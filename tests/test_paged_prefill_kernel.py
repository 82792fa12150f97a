"""Fused paged prefill-chunk kernel + multi-page decode tile parity.

The fused kernel (``kernels/paged_prefill_attention.py``) must match the
XLA gather oracle (the path ``attend_prefill_chunk_paged`` falls back to)
bit-for-bit up to float tolerance on every VALID query row, across the
chunk-boundary shapes the engine produces: a chunk whose start straddles a
page edge, ``valid == 0`` inactive rows, the first chunk of a prompt
(empty page prefix), and a final partial chunk.  Rows past ``valid`` are
garbage in BOTH paths and excluded (callers ignore them).

The decode half: multi-page kv tiles (``pages_per_tile`` > 1) must be a
pure perf reshaping — identical outputs at small block sizes with ragged
per-sequence ``kv_valid``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# kernel-executing tests carry the `pallas` marker individually; the pure
# XLA oracle/gather tests stay unmarked so `-m "not pallas"` keeps them


def _mk_paged_prefill_case(rng, *, B, H, KVH, C, D, bs, nb, starts, valid):
    """Random page pool (unowned pages hold garbage on purpose), permuted
    block tables, chunk q/k/v, plus a densified prefix for the from-scratch
    oracle."""
    N = 4 * B * nb
    q = rng.standard_normal((B, H, C, D)).astype(np.float32)
    kp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    vp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    ck = rng.standard_normal((B, KVH, C, D)).astype(np.float32)
    cv = rng.standard_normal((B, KVH, C, D)).astype(np.float32)
    bt = rng.permutation(N)[:B * nb].reshape(B, nb).astype(np.int32)
    return q, kp, vp, ck, cv, bt, np.asarray(starts, np.int32), \
        np.asarray(valid, np.int32)


def _assert_valid_rows_close(out, want, valid, **tol):
    """Compare only rows < valid[b] (garbage rows differ by design)."""
    for b, n in enumerate(valid):
        if n > 0:
            np.testing.assert_allclose(np.asarray(out[b, :, :n], np.float32),
                                       np.asarray(want[b, :, :n], np.float32),
                                       **tol)


# ---------------------------------------------------------------------------
# fused paged prefill-chunk kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pages_per_tile", [None, 1, 2])
@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.pallas
def test_paged_prefill_parity_across_chunk_boundaries(bs, pages_per_tile):
    """Float kernel == gather oracle for: first chunk (empty prefix), a
    prefix ending mid-page (chunk start straddles a page edge), a
    page-aligned prefix, an inactive row, and a final partial chunk."""
    rng = np.random.default_rng(20)
    C, nb = 16, 6
    starts = [0, 19 if bs == 8 else 21, 2 * bs, 11, 0]
    valid = [C, C, 5, 0, 3]          # full / full / partial / inactive / part
    q, kp, vp, ck, cv, bt, st, vd = _mk_paged_prefill_case(
        rng, B=5, H=4, KVH=2, C=C, D=32, bs=bs, nb=nb,
        starts=starts, valid=valid)
    out = ops.paged_prefill_attention(q, kp, vp, ck, cv, bt, st, vd,
                                      pages_per_tile=pages_per_tile)
    want = ref.paged_prefill_attention_ref(jnp.asarray(q), kp, vp, ck, cv,
                                           bt, st, vd)
    _assert_valid_rows_close(out, want, valid, rtol=2e-5, atol=2e-5)


@pytest.mark.pallas
def test_paged_prefill_oracle_matches_dense_from_scratch():
    """The gather oracle itself cross-checked against plain full causal
    attention over [prefix ; chunk]: chunk row c == full-sequence row
    start + c when the chunk completes the prompt."""
    rng = np.random.default_rng(21)
    B, H, KVH, C, D, bs, nb = 1, 4, 2, 8, 16, 8, 4
    start = 13                      # straddles a page edge
    L = start + C
    k_full = rng.standard_normal((B, KVH, L, D)).astype(np.float32)
    v_full = rng.standard_normal((B, KVH, L, D)).astype(np.float32)
    q_full = rng.standard_normal((B, H, L, D)).astype(np.float32)

    # scatter the prefix into a page pool
    N = 8
    kp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    vp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    bt = rng.permutation(N)[:nb].reshape(1, nb).astype(np.int32)
    for p in range(start):
        kp[bt[0, p // bs], :, p % bs] = k_full[0, :, p]
        vp[bt[0, p // bs], :, p % bs] = v_full[0, :, p]

    q = q_full[:, :, start:]
    ck = k_full[:, :, start:]
    cv = v_full[:, :, start:]
    st = np.array([start], np.int32)
    vd = np.array([C], np.int32)

    full = ref.flash_attention_ref(jnp.asarray(q_full), k_full, v_full,
                                   causal=True)[:, :, start:]
    for fn in (ref.paged_prefill_attention_ref, ops.paged_prefill_attention):
        got = fn(jnp.asarray(q), kp, vp, ck, cv, bt, st, vd)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(full, np.float32),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages_per_tile", [None, 2])
@pytest.mark.pallas
def test_paged_prefill_quant_parity(pages_per_tile):
    """int8 page pool + per-row scale pages (prefix dequantized in VMEM,
    in-chunk k/v float) == the quant gather oracle."""
    rng = np.random.default_rng(22)
    B, H, KVH, C, D, bs, nb = 3, 4, 2, 16, 32, 8, 6
    N = 30
    q = rng.standard_normal((B, H, C, D)).astype(np.float32)
    kq = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    vq = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    ks = (rng.random((N, KVH, bs)) * 0.1).astype(np.float32)
    vs = (rng.random((N, KVH, bs)) * 0.1).astype(np.float32)
    ck = rng.standard_normal((B, KVH, C, D)).astype(np.float32)
    cv = rng.standard_normal((B, KVH, C, D)).astype(np.float32)
    bt = rng.permutation(N)[:B * nb].reshape(B, nb).astype(np.int32)
    starts = np.array([0, 19, 48], np.int32)   # empty / mid-page / aligned
    valid = np.array([16, 7, 0], np.int32)
    out = ops.paged_prefill_attention_quant(q, kq, vq, ks, vs, ck, cv, bt,
                                            starts, valid,
                                            pages_per_tile=pages_per_tile)
    want = ref.paged_prefill_attention_quant_ref(jnp.asarray(q), kq, vq, ks,
                                                 vs, ck, cv, bt, starts, valid)
    _assert_valid_rows_close(out, want, valid, rtol=2e-4, atol=2e-4)


@pytest.mark.pallas
def test_paged_prefill_sentinel_blocks_ignored():
    """Logical blocks at/past the prefix may hold sentinel (out-of-pool)
    ids — required by the engine, whose tables are sentinel-padded."""
    rng = np.random.default_rng(23)
    B, H, KVH, C, D, bs, nb = 1, 2, 2, 8, 16, 8, 4
    q, kp, vp, ck, cv, bt, st, vd = _mk_paged_prefill_case(
        rng, B=B, H=H, KVH=KVH, C=C, D=D, bs=bs, nb=nb,
        starts=[11], valid=[C])
    out1 = ops.paged_prefill_attention(q, kp, vp, ck, cv, bt, st, vd)
    bt_sent = bt.copy()
    bt_sent[0, 2:] = kp.shape[0] + 7      # sentinel >= pool size
    out2 = ops.paged_prefill_attention(q, kp, vp, ck, cv, bt_sent, st, vd)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# ---------------------------------------------------------------------------
# q-tiling: chunks wider than one q tile split across grid steps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [256, 512])
@pytest.mark.pallas
def test_paged_prefill_q_tiled_long_chunk_parity(chunk):
    """Chunks past one q tile (prefill_chunk_tokens=512+) split across the
    q grid dimension (auto_q_tile -> 128 rows) and must match the gather
    oracle on every valid row — heterogeneous starts/valid, block_size 8,
    a ragged row ending mid-tile, and an inactive row."""
    from repro.kernels.paged_prefill_attention import auto_q_tile
    assert auto_q_tile(chunk) == 128          # > 1 q tile per chunk
    rng = np.random.default_rng(30)
    bs = 8
    nb = (40 + chunk + bs - 1) // bs + 1
    starts = [40, 7, 0]
    valid = [chunk, chunk - 77, 0]            # full / mid-tile ragged / dead
    q, kp, vp, ck, cv, bt, st, vd = _mk_paged_prefill_case(
        rng, B=3, H=4, KVH=2, C=chunk, D=32, bs=bs, nb=nb,
        starts=starts, valid=valid)
    out = ops.paged_prefill_attention(q, kp, vp, ck, cv, bt, st, vd)
    want = ref.paged_prefill_attention_ref(jnp.asarray(q), kp, vp, ck, cv,
                                           bt, st, vd)
    _assert_valid_rows_close(out, want, valid, rtol=2e-5, atol=2e-5)


@pytest.mark.pallas
def test_paged_prefill_explicit_q_tile_matches_single_tile():
    """q_tile is a pure tiling choice: explicit narrow tiles == the
    one-tile layout bit-for-bit on valid rows (float and int8 twins)."""
    rng = np.random.default_rng(31)
    B, H, KVH, C, D, bs, nb = 2, 4, 2, 64, 32, 8, 12
    starts, valid = [19, 0], [C, C - 5]
    q, kp, vp, ck, cv, bt, st, vd = _mk_paged_prefill_case(
        rng, B=B, H=H, KVH=KVH, C=C, D=D, bs=bs, nb=nb,
        starts=starts, valid=valid)
    base = ops.paged_prefill_attention(q, kp, vp, ck, cv, bt, st, vd,
                                       q_tile=C)
    for qt in (16, 32):
        tiled = ops.paged_prefill_attention(q, kp, vp, ck, cv, bt, st, vd,
                                            q_tile=qt)
        _assert_valid_rows_close(tiled, base, valid, rtol=1e-6, atol=1e-6)

    N = kp.shape[0]
    ks = (rng.random((N, KVH, bs)) * 0.1).astype(np.float32)
    vs = (rng.random((N, KVH, bs)) * 0.1).astype(np.float32)
    kq = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    vq = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    qbase = ops.paged_prefill_attention_quant(q, kq, vq, ks, vs, ck, cv, bt,
                                              st, vd, q_tile=C)
    qtiled = ops.paged_prefill_attention_quant(q, kq, vq, ks, vs, ck, cv, bt,
                                               st, vd, q_tile=16)
    _assert_valid_rows_close(qtiled, qbase, valid, rtol=1e-6, atol=1e-6)


@pytest.mark.pallas
def test_engine_long_chunk_q_tiled_token_parity():
    """End-to-end: a paged-pallas engine at prefill_chunk_tokens=256 (the
    q-tiled kernel path, bucket 256 > one 128-row tile) produces the same
    tokens as the dense xla backend for a long prompt."""
    from repro.configs import ARCHITECTURES
    from repro.core.request import Request
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine, EngineConfig

    cfg = ARCHITECTURES["granite-3-2b"].reduced(num_layers=1, d_model=64)
    model_ = build_model(cfg)
    params = model_.init(jax.random.key(0))
    rng = np.random.default_rng(32)
    prompts = [rng.integers(0, 100, size=n).tolist() for n in (300, 9)]

    def run(backend):
        eng = ContinuousBatchingEngine(
            model_, params,
            EngineConfig(max_slots=2, max_seq_len=384, block_size=8,
                         prefill_chunk_tokens=256,
                         attention_backend=backend),
            model_name="m1")
        reqs = [Request(prompt_tokens=p, model="m1", slo=1e9,
                        max_new_tokens=3) for p in prompts]
        for r in reqs:
            assert eng.admit(r)
        for _ in range(40):
            eng.step()
            if all(r.finished() for r in reqs):
                break
        assert all(r.finished() for r in reqs)
        return [r.output_tokens for r in reqs]

    assert run("paged-pallas") == run("xla")


# ---------------------------------------------------------------------------
# multi-page decode tiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("pages_per_tile", [1, 2, 4, None, 16])
@pytest.mark.pallas
def test_paged_decode_multi_page_tiles(bs, pages_per_tile):
    """pages_per_tile is a pure perf reshaping: identical outputs for
    ragged kv_valid (1 token / mid-page / full pool) at small block
    sizes."""
    rng = np.random.default_rng(24)
    B, H, KVH, S, D = 3, 8, 2, 64, 32
    nb = S // bs
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    k = rng.standard_normal((B, KVH, S, D)).astype(np.float32)
    v = rng.standard_normal((B, KVH, S, D)).astype(np.float32)
    N = 4 * B * nb
    perm = rng.permutation(N)[:B * nb].reshape(B, nb)
    kp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    vp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    for b in range(B):
        for i in range(nb):
            kp[perm[b, i]] = k[b, :, i * bs:(i + 1) * bs]
            vp[perm[b, i]] = v[b, :, i * bs:(i + 1) * bs]
    kv_valid = np.array([1, bs + 3, S], np.int32)   # ragged
    out = ops.paged_decode_attention(q, kp, vp, perm.astype(np.int32),
                                     kv_valid, pages_per_tile=pages_per_tile)
    want = ref.decode_attention_ref(jnp.asarray(q), k, v, kv_valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.pallas
def test_paged_decode_quant_multi_page_tiles():
    """int8 twin with pages_per_tile > 1 == dequantized oracle."""
    rng = np.random.default_rng(25)
    B, H, KVH, S, D, bs = 2, 4, 2, 48, 32, 8
    nb, N = S // bs, 24
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    kq = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    vq = rng.integers(-127, 128, size=(N, KVH, bs, D)).astype(np.int8)
    ks = (rng.random((N, KVH, bs)) * 0.1).astype(np.float32)
    vs = (rng.random((N, KVH, bs)) * 0.1).astype(np.float32)
    bt = rng.permutation(N)[:B * nb].reshape(B, nb).astype(np.int32)
    lengths = np.array([S, 13], np.int32)
    from repro.kernels.paged_decode_attention import gather_kv_pages_fused
    kd, vd = gather_kv_pages_fused(jnp.asarray(kq), jnp.asarray(vq),
                                   jnp.asarray(bt))
    ksd, vsd = gather_kv_pages_fused(jnp.asarray(ks), jnp.asarray(vs),
                                     jnp.asarray(bt))
    k = np.asarray(kd, np.float32) * np.asarray(ksd)[..., None]
    v = np.asarray(vd, np.float32) * np.asarray(vsd)[..., None]
    want = ref.decode_attention_ref(jnp.asarray(q), k, v, lengths)
    for P in (2, 3):
        out = ops.paged_decode_attention_quant(q, kq, vq, ks, vs, bt,
                                               lengths, pages_per_tile=P)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_gather_kv_pages_fused_matches_single():
    """The stacked (fused) gather == two independent gathers, value and
    scale shapes, sentinel entries included."""
    from repro.kernels.paged_decode_attention import (gather_kv_pages,
                                                     gather_kv_pages_fused)
    rng = np.random.default_rng(26)
    N, KVH, bs, D = 10, 2, 8, 16
    kp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    vp = rng.standard_normal((N, KVH, bs, D)).astype(np.float32)
    sp = rng.standard_normal((N, KVH, bs)).astype(np.float32)
    tp = rng.standard_normal((N, KVH, bs)).astype(np.float32)
    bt = np.array([[0, 3, N + 5], [7, 1, 2]], np.int32)  # incl. sentinel
    for a, b in ((kp, vp), (sp, tp)):
        fa, fb = gather_kv_pages_fused(jnp.asarray(a), jnp.asarray(b),
                                       jnp.asarray(bt))
        np.testing.assert_array_equal(np.asarray(fa),
                                      np.asarray(gather_kv_pages(
                                          jnp.asarray(a), jnp.asarray(bt))))
        np.testing.assert_array_equal(np.asarray(fb),
                                      np.asarray(gather_kv_pages(
                                          jnp.asarray(b), jnp.asarray(bt))))


# ---------------------------------------------------------------------------
# engine-level: explicit pages_per_tile stays token-identical
# ---------------------------------------------------------------------------

@pytest.mark.pallas
def test_engine_pages_per_tile_token_parity():
    """EngineConfig.pages_per_tile (multi-page kv tiles in BOTH paged
    kernels) must not change a single token vs the default."""
    from repro.configs import ARCHITECTURES
    from repro.core.request import Request
    from repro.serving import ContinuousBatchingEngine, EngineConfig

    cfg = ARCHITECTURES["granite-3-2b"].reduced(num_layers=1, d_model=64)
    model_ = __import__("repro.models", fromlist=["build_model"]) \
        .build_model(cfg)
    params = model_.init(jax.random.key(0))
    rng = np.random.default_rng(27)
    prompts = [rng.integers(0, 100, size=n).tolist() for n in (3, 21)]

    def run(pages_per_tile):
        eng = ContinuousBatchingEngine(
            model_, params,
            EngineConfig(max_slots=2, max_seq_len=64, block_size=8,
                         prefill_chunk_tokens=16,
                         attention_backend="paged-pallas",
                         pages_per_tile=pages_per_tile),
            model_name="m1")
        reqs = [Request(prompt_tokens=p, model="m1", slo=1e9,
                        max_new_tokens=4) for p in prompts]
        for r in reqs:
            assert eng.admit(r)
        for _ in range(40):
            eng.step()
            if all(r.finished() for r in reqs):
                break
        assert all(r.finished() for r in reqs)
        assert eng.model.cfg.paged_pages_per_tile == pages_per_tile
        return [r.output_tokens for r in reqs]

    assert run(None) == run(2) == run(1)
