"""Pallas TPU paged decode-attention kernel (PagedAttention-style KV).

The KV cache is a single global page pool shared by every sequence in the
engine:

  k_pages / v_pages : (num_blocks, KVH, block_size, D)

Each sequence owns a list of physical pages named by its ``BlockManager``
block table; logical token position ``p`` of sequence ``b`` lives in page
``block_table[b, p // block_size]`` at row ``p % block_size``.  Pages are
physically non-contiguous, so the eviction / swapping / admission LSOs can
reclaim and reassign HBM at block granularity instead of per-slot
``max_seq_len`` stripes.

Grid (batch, kv_head, kv_tile).  The block table and per-sequence
``lengths`` ride in scalar-prefetch SMEM (``PrefetchScalarGridSpec``), so
the k/v ``index_map`` can translate logical block ids into physical page
ids BEFORE the DMA is issued — the gather happens in the pipeline's
address computation, not as a materialized copy.  Each kv tile fetches
``pages_per_tile`` pages (replicated k/v inputs whose index_maps read
consecutive block-table entries), so small ``block_size`` pools still fill
MXU tiles; ``pages_per_tile=None`` auto-derives the width from
``block_size`` (``auto_pages_per_tile`` targets 128-row tiles).  As in the
dense kernel, the whole GQA head-group's queries ride along in one tile;
tiles fully past ``lengths[b]`` skip compute via ``pl.when`` and skip
their DMAs too (dead logical blocks clamp to the last live one in the
index_map, so the unchanged block index pipeline-elides the copy).

``lengths`` counts every valid cache slot INCLUDING the newest token (the
same inclusive convention as ``decode_attention`` /
``decode_attention_quant`` — see those docstrings).

The chunked-prefill twin (same page pool, chunk queries, online softmax
over prefix pages + the causal in-chunk segment) lives in
``kernels/paged_prefill_attention.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

# Target kv-tile rows per grid step: one MXU-aligned 128-row tile.  A pool
# with block_size 8 fetches 16 pages per step, block_size 128+ fetches 1.
_TARGET_TILE_ROWS = 128


def auto_pages_per_tile(block_size: int, nb: int) -> int:
    """Pages fetched per grid step so a kv tile approaches 128 rows
    (``_TARGET_TILE_ROWS``) without exceeding the table width ``nb``."""
    p = max(1, _TARGET_TILE_ROWS // max(block_size, 1))
    return max(1, min(p, nb))


def _pad_block_table(block_table: jax.Array, num_blocks: int,
                     width: int) -> jax.Array:
    """Clamp sentinel entries (>= num_blocks, marking unallocated logical
    blocks) to a real page and right-pad the table to ``width`` so every
    ``t * P + p`` index the replicated page specs compute stays in range.
    Clamped/padded entries are masked out by ``lengths`` / ``starts``."""
    bt = _clamp_table(block_table, num_blocks)
    nb = bt.shape[1]
    if width > nb:
        bt = jnp.pad(bt, ((0, 0), (0, width - nb)))
    return bt


def _clamp_table(block_table: jax.Array, num_blocks: int) -> jax.Array:
    """Sentinel entries are clamped to a real page so gathers never address
    out of range; their contents are masked out by ``lengths``."""
    return jnp.minimum(block_table.astype(jnp.int32), num_blocks - 1)


def _live_block_index(logical: jax.Array, tokens: jax.Array,
                      block_size: int, width: int) -> jax.Array:
    """Clamp a logical block index to the LAST LIVE block of a sequence
    holding ``tokens`` valid tokens (and to the padded table width).

    Used inside the page index_maps: tiles wholly past the live prefix
    resolve to the same page as the last live block, so consecutive grid
    steps see an unchanged block index and the Pallas pipeline SKIPS the
    dead tiles' DMAs entirely (``pl.when`` alone only skips compute, not
    the fetch).  The duplicated fetches read already-masked positions, so
    contents never leak into the output."""
    last_live = jnp.maximum((tokens + block_size - 1) // block_size, 1) - 1
    return jnp.minimum(jnp.minimum(logical, last_live), width - 1)


def _online_softmax_update(s, v, m_scr, l_scr, acc_scr):
    """One online-softmax accumulation step shared by the paged decode and
    prefill-chunk kernels: fold score tile ``s`` (rows_q, rows_kv) and
    value tile ``v`` (rows_kv, D) into the running max / denominator /
    accumulator scratch."""
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _assemble_kv_tile(k_refs, v_refs, ks_refs, vs_refs, P: int):
    """Concatenate the P replicated page refs into one (P*bs, D) f32 k/v
    tile, fusing the per-row int8 dequant in VMEM when scale refs are
    given (shared by the decode and prefill-chunk kernels)."""
    if ks_refs is not None:
        k_parts = [k_refs[p][0, 0].astype(jnp.float32)
                   * ks_refs[p][0, 0].astype(jnp.float32)[:, None]
                   for p in range(P)]
        v_parts = [v_refs[p][0, 0].astype(jnp.float32)
                   * vs_refs[p][0, 0].astype(jnp.float32)[:, None]
                   for p in range(P)]
    else:
        k_parts = [k_refs[p][0, 0].astype(jnp.float32) for p in range(P)]
        v_parts = [v_refs[p][0, 0].astype(jnp.float32) for p in range(P)]
    k = k_parts[0] if P == 1 else jnp.concatenate(k_parts, axis=0)
    v = v_parts[0] if P == 1 else jnp.concatenate(v_parts, axis=0)
    return k, v


def _make_decode_kernel(*, P: int, scale: float, block_size: int,
                        quant: bool):
    """Kernel body closure.  Tensor-ref layout after the 2 scalar-prefetch
    refs (block table, lengths):
      q, k_page*P, v_page*P, [k_scale*P, v_scale*P,] o, m_scr, l_scr, acc_scr
    """

    def kernel(bt_ref, len_ref, q_ref, *refs):
        del bt_ref  # consumed by the index_maps (page translation)
        k_refs = refs[:P]
        v_refs = refs[P:2 * P]
        if quant:
            ks_refs = refs[2 * P:3 * P]
            vs_refs = refs[3 * P:4 * P]
            o_ref, m_scr, l_scr, acc_scr = refs[4 * P:]
        else:
            ks_refs = vs_refs = None
            o_ref, m_scr, l_scr, acc_scr = refs[2 * P:]

        b = pl.program_id(0)
        i = pl.program_id(2)
        nt = pl.num_programs(2)
        length = len_ref[b]  # valid tokens in this sequence (incl. newest)

        @pl.when(i == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        tile_rows = P * block_size
        k_start = i * tile_rows

        @pl.when(k_start < length)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32)      # (group, d)
            # per-row scales live in their own scale pages; the dequant
            # happens in VMEM (the HBM read stays int8 + scales)
            k, v = _assemble_kv_tile(k_refs, v_refs, ks_refs, vs_refs, P)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < length, s, NEG_INF)
            _online_softmax_update(s, v, m_scr, l_scr, acc_scr)

        @pl.when(i == nt - 1)
        def _finalize():
            denom = jnp.maximum(l_scr[...], 1e-20)
            o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)

    return kernel


def _decode_call(q, k_pages, v_pages, block_table, lengths, scale_pages, *,
                 pages_per_tile, interpret):
    """Shared pallas_call builder for the float / int8 twins
    (``scale_pages`` is None or the (k_scale, v_scale) pair)."""
    B, H, D = q.shape
    N, KVH, bs, _ = k_pages.shape
    nb = block_table.shape[1]
    assert H % KVH == 0
    group = H // KVH
    quant = scale_pages is not None
    scale = 1.0 / math.sqrt(D)

    P = pages_per_tile or auto_pages_per_tile(bs, nb)
    P = max(1, min(P, nb))
    nt = -(-nb // P)
    W = nt * P
    qg = q.reshape(B, KVH, group, D)
    bt = _pad_block_table(block_table, N, W)

    def _q_idx(b, h, i, bt_ref, len_ref):
        return (b, h, 0, 0)

    def _page_idx(b, h, i, bt_ref, len_ref, *, p):
        # logical block i*P+p of sequence b -> physical page; blocks past
        # the live prefix clamp to the last live block so dead tiles keep
        # an unchanged index and their DMAs are pipeline-skipped
        idx = _live_block_index(i * P + p, len_ref[b], bs, W)
        return (bt_ref[b, idx], h, 0, 0)

    def _scale_idx(b, h, i, bt_ref, len_ref, *, p):
        idx = _live_block_index(i * P + p, len_ref[b], bs, W)
        return (bt_ref[b, idx], h, 0)

    page_spec = lambda p: pl.BlockSpec(  # noqa: E731
        (1, 1, bs, D), functools.partial(_page_idx, p=p))
    in_specs = [pl.BlockSpec((1, 1, group, D), _q_idx)]
    in_specs += [page_spec(p) for p in range(P)]
    in_specs += [page_spec(p) for p in range(P)]
    inputs = [qg] + [k_pages] * P + [v_pages] * P
    if quant:
        k_scale_pages, v_scale_pages = scale_pages
        sspec = lambda p: pl.BlockSpec(  # noqa: E731
            (1, 1, bs), functools.partial(_scale_idx, p=p))
        in_specs += [sspec(p) for p in range(P)]
        in_specs += [sspec(p) for p in range(P)]
        inputs += [k_scale_pages] * P + [v_scale_pages] * P

    kernel = _make_decode_kernel(P=P, scale=scale, block_size=bs, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + lengths, prefetched to SMEM
        grid=(B, KVH, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group, D), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, group, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=("paged_decode_attention_quant" if quant
              else "paged_decode_attention"),
    )(bt, lengths.astype(jnp.int32), *inputs)
    return out.reshape(B, H, D)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           lengths: jax.Array, *,
                           pages_per_tile: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_pages/v_pages: (N, KVH, bs, D); block_table: (B, nb)
    physical page ids per logical block (entries >= N are sentinels for
    unallocated blocks); lengths: (B,) valid tokens INCLUDING the newest.
    ``pages_per_tile=None`` auto-derives the kv-tile width from
    ``block_size``.  Returns (B, H, D)."""
    return _decode_call(q, k_pages, v_pages, block_table, lengths, None,
                        pages_per_tile=pages_per_tile, interpret=interpret)


def paged_decode_attention_quant(q: jax.Array, k_pages: jax.Array,
                                 v_pages: jax.Array, k_scale_pages: jax.Array,
                                 v_scale_pages: jax.Array,
                                 block_table: jax.Array, lengths: jax.Array, *,
                                 pages_per_tile: int | None = None,
                                 interpret: bool = False) -> jax.Array:
    """int8 variant: k/v pages int8 (N, KVH, bs, D), scale pages
    (N, KVH, bs).  Same block-table / lengths / tile conventions as
    ``paged_decode_attention``."""
    return _decode_call(q, k_pages, v_pages, block_table, lengths,
                        (k_scale_pages, v_scale_pages),
                        pages_per_tile=pages_per_tile, interpret=interpret)


def gather_kv_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """XLA gather path: densify a sequence's pages via its block table.

    pages: (N, KVH, bs, D) [or (N, KVH, bs) for scales]; block_table:
    (B, nb) with sentinel entries >= N (clamped — their garbage contents
    must be masked by ``lengths`` downstream).
    Returns (B, KVH, nb * bs, D) [or (B, KVH, nb * bs)]: logical position p
    lands at row p (= block p // bs, offset p % bs).
    """
    N = pages.shape[0]
    g = pages[_clamp_table(block_table, N)]   # (B, nb, KVH, bs, ...)
    g = jnp.moveaxis(g, 2, 1)                 # (B, KVH, nb, bs, ...)
    B, KVH, nb, bs = g.shape[:4]
    return g.reshape((B, KVH, nb * bs) + g.shape[4:])


def gather_kv_pages_fused(a_pages: jax.Array, b_pages: jax.Array,
                          block_table: jax.Array):
    """One STACKED gather densifying two same-shaped page pools (k and v,
    or the k/v scale pair) through the block table — halves the gather
    count of the XLA fallback / oracle paths, which previously issued one
    gather per pool leaf (four on the int8 path).

    a_pages/b_pages: (N, KVH, bs, ...); returns the two
    (B, KVH, nb * bs, ...) dense views (same layout as
    ``gather_kv_pages``).

    Tradeoff: the ``stack`` nominally touches both WHOLE pools (2N pages)
    before the gather picks B*nb of them, trading copy bandwidth for
    gather count when XLA doesn't sink the gather through the concat.
    That's acceptable where this runs — the CPU oracle / ``paged-xla``
    parity backend — and the serving hot path (``paged-pallas``) never
    gathers at all: both paged kernels translate pages in their
    index_maps.
    """
    N = a_pages.shape[0]
    stacked = jnp.stack([a_pages, b_pages], axis=1)  # (N, 2, KVH, bs, ...)
    g = stacked[_clamp_table(block_table, N)]        # (B, nb, 2, KVH, bs, ...)
    g = jnp.moveaxis(g, 2, 0)                        # (2, B, nb, KVH, bs, ...)
    g = jnp.moveaxis(g, 3, 2)                        # (2, B, KVH, nb, bs, ...)
    two, B, KVH, nb, bs = g.shape[:5]
    g = g.reshape((two, B, KVH, nb * bs) + g.shape[5:])
    return g[0], g[1]
