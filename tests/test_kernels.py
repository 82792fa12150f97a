"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes/dtypes
(interpret=True executes the Pallas kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.pallas


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,Lq,Lkv,D,bq,bk", [
    (1, 4, 4, 64, 64, 32, 32, 32),     # MHA square
    (2, 8, 2, 100, 100, 64, 32, 32),   # GQA, non-multiple lengths (padding)
    (1, 4, 1, 33, 65, 16, 16, 16),     # MQA, ragged
])
def test_flash_attention_causal(B, H, KVH, Lq, Lkv, D, bq, bk, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, Lq, D), dtype)
    k = jax.random.normal(ks[1], (B, KVH, Lkv, D), dtype)
    v = jax.random.normal(ks[2], (B, KVH, Lkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [8, 17, 64])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.key(1), 3)
    B, H, KVH, L, D = 2, 4, 2, 80, 32
    q = jax.random.normal(ks[0], (B, H, L, D))
    k = jax.random.normal(ks[1], (B, KVH, L, D))
    v = jax.random.normal(ks[2], (B, KVH, L, D))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=16, block_k=16)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,S,D,bk", [
    (2, 8, 2, 300, 64, 64),
    (1, 4, 4, 17, 32, 8),
    (3, 6, 1, 128, 16, 32),
])
def test_decode_attention(B, H, KVH, S, D, bk, dtype):
    ks = jax.random.split(jax.random.key(2), 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, KVH, S, D), dtype)
    v = jax.random.normal(ks[2], (B, KVH, S, D), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1, jnp.int32)
    out = ops.decode_attention(q, k, v, lengths, block_k=bk)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_respects_lengths():
    """Tokens beyond `lengths` must not influence the output."""
    ks = jax.random.split(jax.random.key(3), 3)
    B, H, KVH, S, D = 1, 2, 2, 64, 16
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, KVH, S, D))
    v = jax.random.normal(ks[2], (B, KVH, S, D))
    lengths = jnp.array([20], jnp.int32)
    out1 = ops.decode_attention(q, k, v, lengths)
    k2 = k.at[:, :, 20:].set(999.0)
    v2 = v.at[:, :, 20:].set(-999.0)
    out2 = ops.decode_attention(q, k2, v2, lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def _paged_from_dense(k_dense, v_dense, block_size, num_pool_blocks, rng):
    """Scatter dense (B, KVH, S, D) k/v into a page pool through a random
    (non-contiguous) page assignment; returns (k_pages, v_pages, block_table)."""
    B, KVH, S, D = k_dense.shape
    nb = S // block_size
    assert nb * block_size == S
    perm = rng.permutation(num_pool_blocks)[:B * nb].reshape(B, nb)
    k_pages = rng.standard_normal((num_pool_blocks, KVH, block_size, D)) \
        .astype(k_dense.dtype)  # unowned pages hold garbage on purpose
    v_pages = rng.standard_normal((num_pool_blocks, KVH, block_size, D)) \
        .astype(v_dense.dtype)
    for b in range(B):
        for i in range(nb):
            k_pages[perm[b, i]] = k_dense[b, :, i * block_size:(i + 1) * block_size]
            v_pages[perm[b, i]] = v_dense[b, :, i * block_size:(i + 1) * block_size]
    return k_pages, v_pages, perm.astype(np.int32)


@pytest.mark.parametrize("B,H,KVH,S,D,bs", [
    (2, 8, 2, 64, 32, 16),
    (3, 4, 4, 40, 16, 8),
    (1, 6, 1, 24, 64, 4),
])
def test_paged_decode_attention(B, H, KVH, S, D, bs):
    """Block-table kernel == dense oracle through a permuted page pool."""
    rng = np.random.default_rng(10)
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    k = rng.standard_normal((B, KVH, S, D)).astype(np.float32)
    v = rng.standard_normal((B, KVH, S, D)).astype(np.float32)
    kp, vp, bt = _paged_from_dense(k, v, bs, 4 * B * (S // bs), rng)
    lengths = rng.integers(1, S + 1, size=B).astype(np.int32)
    out = ops.paged_decode_attention(q, kp, vp, bt, lengths)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)
    # and the XLA gather reference agrees with both
    want2 = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(want2, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_attention_sentinel_blocks_ignored():
    """Logical blocks past `lengths` may hold sentinel (out-of-pool) page
    ids — required by the engine, whose tables are sentinel-padded."""
    rng = np.random.default_rng(11)
    B, H, KVH, S, D, bs = 2, 4, 2, 32, 16, 8
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    k = rng.standard_normal((B, KVH, S, D)).astype(np.float32)
    v = rng.standard_normal((B, KVH, S, D)).astype(np.float32)
    kp, vp, bt = _paged_from_dense(k, v, bs, 16, rng)
    lengths = np.array([7, 9], np.int32)   # needs 1 / 2 pages only
    out1 = ops.paged_decode_attention(q, kp, vp, bt, lengths)
    bt_sent = bt.copy()
    bt_sent[0, 1:] = 16   # sentinel = pool size
    bt_sent[1, 2:] = 16
    out2 = ops.paged_decode_attention(q, kp, vp, bt_sent, lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_attention_quant():
    """int8 page pool with per-row scale pages == dequantized oracle."""
    rng = np.random.default_rng(12)
    B, H, KVH, S, D, bs = 2, 8, 2, 48, 32, 8
    nb, N = S // bs, 24
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    kq = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    vq = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    ks = (rng.random((N, KVH, bs)) * 0.1).astype(np.float32)
    vs = (rng.random((N, KVH, bs)) * 0.1).astype(np.float32)
    bt = rng.permutation(N)[:B * nb].reshape(B, nb).astype(np.int32)
    lengths = np.array([S, 13], np.int32)
    out = ops.paged_decode_attention_quant(q, kq, vq, ks, vs, bt, lengths)
    from repro.kernels.paged_decode_attention import gather_kv_pages
    k = np.asarray(gather_kv_pages(jnp.asarray(kq), jnp.asarray(bt)), np.float32) \
        * np.asarray(gather_kv_pages(jnp.asarray(ks), jnp.asarray(bt)))[..., None]
    v = np.asarray(gather_kv_pages(jnp.asarray(vq), jnp.asarray(bt)), np.float32) \
        * np.asarray(gather_kv_pages(jnp.asarray(vs), jnp.asarray(bt)))[..., None]
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("length", [1, 20, 64])  # incl. the full-cache boundary
def test_decode_attention_quant_length_convention(length):
    """The quant and float decode kernels must consume the SAME (inclusive)
    `lengths` convention: identical int8 content run through the fused
    kernel and through dequantize->float kernel must agree for every
    length, including lengths == S where an off-by-one would read (or drop)
    the final slot."""
    rng = np.random.default_rng(13)
    B, H, KVH, S, D = 2, 4, 2, 64, 16
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    kq = rng.integers(-127, 128, size=(B, KVH, S, D)).astype(np.int8)
    vq = rng.integers(-127, 128, size=(B, KVH, S, D)).astype(np.int8)
    ks = (rng.random((B, KVH, S)) * 0.1).astype(np.float32)
    vs = (rng.random((B, KVH, S)) * 0.1).astype(np.float32)
    lengths = np.array([length, max(1, length - 1)], np.int32)
    from repro.kernels.decode_attention import decode_attention_quant
    out_q = decode_attention_quant(jnp.asarray(q), jnp.asarray(kq),
                                   jnp.asarray(vq), jnp.asarray(ks),
                                   jnp.asarray(vs), jnp.asarray(lengths),
                                   interpret=True)
    k = kq.astype(np.float32) * ks[..., None]
    v = vq.astype(np.float32) * vs[..., None]
    out_f = ops.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_f),
                               rtol=2e-4, atol=2e-4)


# ragged live-page walk: one batch holds an empty slot (1 token), exactly
# one page, one page and a token, a length ending mid-tile and the whole
# table; entries past each slot's live pages are sentinels (= pool size)
_WALK_BS, _WALK_NB = 8, 24


def _live_walk_case(rng, *, H, KVH, D, quant=False):
    bs, nb = _WALK_BS, _WALK_NB
    lengths = np.array([1, bs, bs + 1, 77, 150, nb * bs], np.int32)
    B, N = lengths.size, 3 * lengths.size * nb
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    if quant:
        kp = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
        vp = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    else:
        kp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
        vp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    bt = rng.permutation(N)[:B * nb].reshape(B, nb).astype(np.int32)
    for b, n in enumerate(lengths):
        bt[b, -(-n // bs):] = N
    return q, kp, vp, bt, lengths


@pytest.mark.parametrize("pages_per_tile", [1, None, 16])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("group", [4, 8])
def test_paged_decode_live_page_walk(group, D, pages_per_tile):
    """The live-page walk == the gather oracle for ragged lengths,
    sentinel tails, every tile width and both head_dim routes (64: whole-
    page BlockSpecs; 128: the manual-DMA loop)."""
    rng = np.random.default_rng(100 + group + D)
    KVH = 2
    q, kp, vp, bt, lengths = _live_walk_case(rng, H=group * KVH, KVH=KVH,
                                             D=D)
    out = ops.paged_decode_attention(q, kp, vp, bt, lengths,
                                     pages_per_tile=pages_per_tile)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages_per_tile", [1, None])
@pytest.mark.parametrize("D", [64, 128])
def test_paged_decode_quant_live_page_walk(D, pages_per_tile):
    """int8 twin over the same ragged batch == dequantized oracle."""
    rng = np.random.default_rng(200 + D)
    KVH = 2
    q, kq, vq, bt, lengths = _live_walk_case(rng, H=4 * KVH, KVH=KVH, D=D,
                                             quant=True)
    N = kq.shape[0]
    ks = (rng.random((N, KVH, _WALK_BS)) * 0.1).astype(np.float32)
    vs = (rng.random((N, KVH, _WALK_BS)) * 0.1).astype(np.float32)
    out = ops.paged_decode_attention_quant(q, kq, vq, ks, vs, bt, lengths,
                                           pages_per_tile=pages_per_tile)
    from repro.kernels.paged_decode_attention import gather_kv_pages_fused
    kd, vd = gather_kv_pages_fused(jnp.asarray(kq), jnp.asarray(vq),
                                   jnp.asarray(bt))
    ksd, vsd = gather_kv_pages_fused(jnp.asarray(ks), jnp.asarray(vs),
                                     jnp.asarray(bt))
    k = np.asarray(kd, np.float32) * np.asarray(ksd)[..., None]
    v = np.asarray(vd, np.float32) * np.asarray(vsd)[..., None]
    want = ref.decode_attention_ref(jnp.asarray(q), k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _decode_grid(D, nb, B=4, H=8, KVH=2, bs=16, N=512):
    """The grid of the decode ``pallas_call`` traced for a (B, nb) table."""
    args = (jax.ShapeDtypeStruct((B, H, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((N, KVH, bs, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((N, KVH, bs, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, nb), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    jaxpr = jax.make_jaxpr(ops.paged_decode_attention)(*args)

    def calls(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (eqn,) = calls(jaxpr.jaxpr)
    return tuple(eqn.params["grid_mapping"].grid)


@pytest.mark.parametrize("D", [64, 128])
def test_paged_decode_grid_has_no_kv_head_axis(D):
    """head_dim 128 walks live pages inside one grid step per slot, so its
    grid does not grow with the table; head_dim 64 steps (slot, tile) over
    the table with whole pages.  Neither has a kv-head axis."""
    B, P = 4, 8  # auto tile width: 8 pages of 16 tokens
    grids = {nb: _decode_grid(D, nb, B=B) for nb in (32, 256)}
    if D == 128:
        assert grids == {32: (B,), 256: (B,)}
    else:
        assert grids == {32: (B, 32 // P), 256: (B, 256 // P)}


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("B,L,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 8, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 32, 8, 8, 4, 4, 8),
])
def test_ssd_scan_kernel(B, L, H, P, G, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (B, L, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, L, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, L, G, N), dtype)
    out = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    want = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=5e-4, atol=5e-4)
