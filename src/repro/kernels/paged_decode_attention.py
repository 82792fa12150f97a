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

A kv tile is ``pages_per_tile`` consecutive logical pages of one sequence:
``pages_per_tile * block_size`` tokens of all KVH heads (``None``
auto-derives 128-token tiles, ``auto_pages_per_tile``).  A page is fetched
whole, every kv head at once: ``(KVH, block_size, D)`` is one contiguous
block of the pool.  The query is ``(KVH, group, D)``: each tile is scored
with one dot per kv head, batched over the kv heads, masked to the live
length, and folded into one online softmax (f32, ``1/sqrt(D)``) per query
head.  The block table and ``lengths`` ride in scalar-prefetch SMEM
(``PrefetchScalarGridSpec``); sentinel table entries are clamped to a
real page and masked by ``lengths``.

The route follows the static page shape (``_dma_sliceable``):

* lane-aligned pages (head_dim a multiple of 128, float pools): grid
  ``(batch,)``.  Each step walks its slot's ``ceil(lengths[b] / tile)``
  live tiles in a ``fori_loop``, fetching each live page with one DMA per
  pool (``memory_space=pl.ANY``, ``make_async_copy``) into a double
  buffer: tile ``t + 1``'s pages are in flight while tile ``t`` is
  computed.  Pages past the live length are never fetched; the buffers
  are zeroed once per call so their stale rows stay finite under the mask.
* any other page (head_dim 64, int8 scale pages): Mosaic refuses a DMA
  slice of it (a 128-lane view of the pool would slice, but in the served
  step XLA lays the pool out anew for it: two more pool-sized copies a
  layer), so the grid is ``(batch, kv_tile)`` over the whole table,
  with one whole-page ``BlockSpec`` per page of the tile.  Tiles past
  ``lengths[b]`` skip compute via ``pl.when`` and their DMAs too (their
  index_map clamps to the last live page, and the unchanged block index
  elides the copy), but each still costs a grid step.

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
_LANES = 128

# Target tokens per kv tile: one MXU-aligned 128-row tile per kv head.  A
# pool with block_size 8 takes 16 pages per tile, block_size 128+ takes 1.
_TARGET_TILE_ROWS = 128


def auto_pages_per_tile(block_size: int, nb: int) -> int:
    """Pages per kv tile so a tile approaches 128 tokens
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


def _online_softmax_update(s, v, m_scr, l_scr, acc_scr, p_scale=None):
    """One online-softmax accumulation step shared by the paged decode and
    prefill-chunk kernels: fold score tile ``s`` (rows_q, rows_kv) and
    value tile ``v`` (rows_kv, D) into the running max / denominator /
    accumulator scratch; leading dims of all five are batch dims (one per
    kv head in decode).  ``p_scale`` (..., 1, rows_kv), when given, scales
    each kv row's weight in the accumulator only (int8 v's per-token
    scale)."""
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1)
    pv = p if p_scale is None else p * p_scale
    batch = tuple(range(s.ndim - 2))
    acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
        pv, v, (((s.ndim - 1,), (s.ndim - 2,)), (batch, batch)),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _assemble_kv_tile(k_refs, v_refs, ks_refs, vs_refs, P: int):
    """Concatenate the P replicated page refs into one (P*bs, D) f32 k/v
    tile, fusing the per-row int8 dequant in VMEM when scale refs are
    given (the prefill-chunk kernel's per-head page refs)."""
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


def _dma_sliceable(pool: jax.Array) -> bool:
    """Whether a DMA may slice one page (KVH, bs, D) out of ``pool``:
    Mosaic refuses a slice whose last two dims are not whole tiles of the
    dtype, so a head_dim of 64 or a (KVH, bs) scale page is not."""
    sublanes = 32 // pool.dtype.itemsize
    return pool.shape[-1] % _LANES == 0 and pool.shape[-2] % sublanes == 0


def _make_decode_kernel(*, P: int, block_size: int, width: int, scale: float,
                        quant: bool, manual_dma: bool):
    """Kernel body closure.  Refs after the 2 scalar-prefetch refs (block
    table, lengths) and the q block:

      manual DMA: k_pool, v_pool, o, k_buf, v_buf (two tiles each), sems,
                  m_scr, l_scr, acc_scr
      pipelined:  P page blocks for each of k, v [, k_scale, v_scale], o,
                  m_scr, l_scr, acc_scr
    """
    tile_tokens = P * block_size

    def init(m_scr, l_scr, acc_scr):
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def update(q_ref, tiles, t, length, m_scr, l_scr, acc_scr):
        """Fold kv tile ``t`` ((KVH, P*bs, D) k and v [and (KVH, P*bs)
        scales]) into the running softmax of every query head: one dot
        per kv head, batched over the kv heads."""
        k, v = tiles[0], tiles[1]
        q = q_ref[0].astype(jnp.float32)                  # (KVH, group, D)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        p_scale = None
        if quant:  # per-token scales: k's scale the score, v's the weight
            s = s * tiles[2][:, None, :]
            p_scale = tiles[3][:, None, :]
        pos = t * tile_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, NEG_INF)
        _online_softmax_update(s, v, m_scr, l_scr, acc_scr, p_scale=p_scale)

    def finalize(o_ref, l_scr, acc_scr):
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / denom[..., None]).astype(o_ref.dtype)

    def manual_kernel(bt_ref, len_ref, q_ref, k_pool, v_pool, o_ref, k_buf,
                      v_buf, sems, m_scr, l_scr, acc_scr):
        b = pl.program_id(0)
        length = len_ref[b]  # valid tokens in this sequence (incl. newest)
        live_pages = (length + block_size - 1) // block_size
        n_tiles = (length + tile_tokens - 1) // tile_tokens

        def page_copies(t, slot):
            """(live, copy) for each page DMA of tile ``t`` into buffer
            ``slot``: one whole page, every kv head, per pool."""
            out = []
            for p in range(P):
                logical = t * P + p
                page = bt_ref[b, jnp.minimum(logical, width - 1)]
                rows = pl.ds(p * block_size, block_size)
                for i, (pool, buf) in enumerate(((k_pool, k_buf),
                                                 (v_pool, v_buf))):
                    out.append((logical < live_pages, pltpu.make_async_copy(
                        pool.at[page], buf.at[slot, :, rows],
                        sems.at[slot, i, p])))
            return out

        def start(t, slot):
            for live, copy in page_copies(t, slot):
                pl.when(live)(copy.start)

        def wait(t, slot):
            for live, copy in page_copies(t, slot):
                pl.when(live)(copy.wait)

        # pages past the live length are never fetched, so a buffer row
        # holds an earlier tile's page or, before the first fetch, whatever
        # VMEM held: zero the buffers once per call so a masked row is
        # always finite (0 * NaN would reach the accumulator)
        @pl.when(b == 0)
        def _zero():
            k_buf[...] = jnp.zeros_like(k_buf)
            v_buf[...] = jnp.zeros_like(v_buf)

        init(m_scr, l_scr, acc_scr)

        @pl.when(n_tiles > 0)
        def _first():
            start(0, 0)

        def body(t, carry):
            slot = t % 2

            @pl.when(t + 1 < n_tiles)
            def _prefetch():
                start(t + 1, 1 - slot)

            wait(t, slot)
            tiles = [buf[slot].astype(jnp.float32) for buf in (k_buf, v_buf)]
            update(q_ref, tiles, t, length, m_scr, l_scr, acc_scr)
            return carry

        jax.lax.fori_loop(0, n_tiles, body, 0)
        finalize(o_ref, l_scr, acc_scr)

    def pipelined_kernel(bt_ref, len_ref, q_ref, *refs):
        del bt_ref  # consumed by the index_maps (page translation)
        n_src = 4 if quant else 2
        page_refs = refs[:n_src * P]
        o_ref, m_scr, l_scr, acc_scr = refs[n_src * P:]
        b = pl.program_id(0)
        t = pl.program_id(1)
        length = len_ref[b]

        @pl.when(t == 0)
        def _init():
            init(m_scr, l_scr, acc_scr)

        @pl.when(t * tile_tokens < length)
        def _compute():
            # each pool's P whole pages (KVH, bs, ...) -> (KVH, P*bs, ...)
            pools = [page_refs[i * P:(i + 1) * P] for i in range(n_src)]
            tiles = [jnp.concatenate([r[0].astype(jnp.float32) for r in rs],
                                     axis=1) for rs in pools]
            update(q_ref, tiles, t, length, m_scr, l_scr, acc_scr)

        @pl.when(t == pl.num_programs(1) - 1)
        def _finalize():
            finalize(o_ref, l_scr, acc_scr)

    return manual_kernel if manual_dma else pipelined_kernel


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
    qg = q.reshape(B, KVH, group, D)
    pools = [k_pages, v_pages] + list(scale_pages or ())
    manual_dma = all(_dma_sliceable(a) for a in pools)
    kernel = _make_decode_kernel(P=P, block_size=bs, width=nb, scale=scale,
                                 quant=quant, manual_dma=manual_dma)
    scratch = [pltpu.VMEM((KVH, group), jnp.float32),
               pltpu.VMEM((KVH, group), jnp.float32),
               pltpu.VMEM((KVH, group, D), jnp.float32)]
    if manual_dma:
        grid = (B,)
        per_slot = lambda b, bt_ref, len_ref: (b, 0, 0, 0)  # noqa: E731
        page_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        inputs = pools
        scratch = [pltpu.VMEM((2, KVH, P * bs, D), k_pages.dtype),
                   pltpu.VMEM((2, KVH, P * bs, D), v_pages.dtype),
                   pltpu.SemaphoreType.DMA((2, 2, P))] + scratch
        table = _clamp_table(block_table, N)
        # the buffers are zeroed at slot 0 only: slots run in order
        semantics = ("arbitrary",)
    else:
        nt = -(-nb // P)
        W = nt * P
        grid = (B, nt)
        per_slot = lambda b, t, bt_ref, len_ref: (b, 0, 0, 0)  # noqa: E731

        def _page_idx(b, t, bt_ref, len_ref, *, p, rank):
            # logical block t*P+p of sequence b -> physical page; blocks
            # past the live prefix clamp to the last live block so dead
            # tiles keep an unchanged index and their DMAs are elided
            idx = _live_block_index(t * P + p, len_ref[b], bs, W)
            return (bt_ref[b, idx],) + (0,) * (rank - 1)

        page_specs, inputs = [], []
        for a in pools:
            page_specs += [pl.BlockSpec((1,) + a.shape[1:], functools.partial(
                _page_idx, p=p, rank=a.ndim)) for p in range(P)]
            inputs += [a] * P
        table = _pad_block_table(block_table, N, W)
        semantics = ("parallel", "arbitrary")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + lengths, prefetched to SMEM
        grid=grid,
        in_specs=[pl.BlockSpec((1, KVH, group, D), per_slot)] + page_specs,
        out_specs=pl.BlockSpec((1, KVH, group, D), per_slot),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, group, D), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name=("paged_decode_attention_quant" if quant
              else "paged_decode_attention"),
    )(table, lengths.astype(jnp.int32), qg, *inputs)
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
