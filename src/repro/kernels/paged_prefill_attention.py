"""Pallas TPU paged prefill-chunk attention kernel (flash-style, no gather).

The chunked-prefill serving path attends one right-padded chunk of queries
per sequence against (a) the sequence's already-written KV prefix, which
lives in the global page pool ``(num_blocks, KVH, block_size, D)`` named by
a per-sequence block table, and (b) the chunk's own fresh keys/values
(causal).  The XLA fallback densifies the WHOLE pre-chunk page pool slice
``(B, KVH, nb*bs, D)`` with a gather and concatenates the in-chunk keys —
an O(table) HBM copy per chunk that is quadratic over a long prompt.  This
kernel removes that copy: KV pages stream **in place** through the
SMEM-prefetched block table (the same ``PrefetchScalarGridSpec`` index_map
translation as the paged decode kernel) and an online softmax folds the
page-resident prefix and the causal in-chunk segment into one pass, so
per-chunk HBM reads are proportional to live tokens instead of the padded
pool, with no densified intermediate.

Grid (batch, kv_head, q_tile, prefix_tile + 1).  The GQA head-group's
chunk queries ride in ``(group, q_tile, D)`` tiles — chunks longer than
one tile (``prefill_chunk_tokens=512+``) are split across the third grid
dimension instead of blowing a single VMEM tile; ``auto_q_tile`` targets
128 query rows per tile (chunks <= 128 keep the old one-tile layout).
Every live page is fetched once per KV head per q tile.  Each prefix grid
step fetches ``pages_per_tile`` pages — replicated k/v inputs whose
index_maps read consecutive block-table entries — so small ``block_size``
pools still fill MXU tiles; the final grid step attends the causal
in-chunk segment and finalizes.  Tiles fully past ``starts[b]`` (the
sequence's prefix length) — and whole q tiles past ``valid[b]`` — skip
compute via ``pl.when``; dead prefix tiles skip their DMAs too: the
index_map clamps dead logical blocks to the last live one, so the block
index stops changing and the pipeline elides the copies.

Conventions (mirroring ``attend_prefill_chunk_paged``):
  * q: (B, H, C, D) chunk queries, row ``c`` at absolute position
    ``starts[b] + c``;
  * chunk_k / chunk_v: (B, KVH, C, D) the chunk's OWN keys/values (fresh
    projections — on the int8 path these stay float, exactly like the
    gather fallback, which only dequantizes the page-resident prefix);
  * block_table: (B, nb) physical page ids, sentinel entries >= num_blocks
    for unallocated logical blocks (clamped; masked by ``starts``);
  * starts: (B,) tokens already resident in pages (= the chunk's first
    absolute position); valid: (B,) real tokens in the chunk, 0 marking an
    inactive row whose output the caller ignores.

Every prefix position < starts[b] is visible to every chunk query (chunk
positions are all >= starts[b], so causality holds unconditionally there);
in-chunk key j is visible to query c iff ``j <= c`` and ``j < valid[b]``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_decode_attention import (
    NEG_INF,
    _assemble_kv_tile,
    _live_block_index,
    _online_softmax_update,
    _pad_block_table,
    auto_pages_per_tile,
)


_TARGET_Q_ROWS = 128


def auto_q_tile(chunk_len: int) -> int:
    """Query rows per q tile: the largest divisor of ``chunk_len`` that is
    <= ``_TARGET_Q_ROWS`` (power-of-two chunk buckets land exactly on 128).
    Chunks at or under the target keep the single-tile layout, as do
    awkward lengths whose only divisors are tiny (e.g. primes) — a sliver
    tile would re-fetch every live page once per handful of query rows,
    which is far worse than one wide tile."""
    if chunk_len <= _TARGET_Q_ROWS:
        return chunk_len
    for t in range(_TARGET_Q_ROWS, _TARGET_Q_ROWS // 8, -1):
        if chunk_len % t == 0:
            return t
    return chunk_len


def _make_prefill_kernel(*, P: int, nt: int, scale: float, block_size: int,
                         chunk_len: int, q_tile: int, group: int,
                         quant: bool):
    """Kernel body closure.  Tensor-ref layout after the 3 scalar-prefetch
    refs (block table, starts, valid):
      q, k_page*P, v_page*P, [k_scale*P, v_scale*P,] chunk_k, chunk_v,
      o, m_scr, l_scr, acc_scr

    The q tile is one ``q_tile``-query slice of the whole GQA group,
    (group, q_tile, D), flattened to (group * q_tile, D) rows for the
    matmuls; flattened row r is in-tile query position ``r % q_tile`` of
    head ``r // q_tile``, at absolute chunk position
    ``qi * q_tile + r % q_tile`` (``qi`` = q-tile grid index), so the
    causal chunk mask depends on the row only through that remainder.
    """
    rows_q = group * q_tile

    def kernel(bt_ref, st_ref, vd_ref, q_ref, *refs):
        del bt_ref  # consumed by the index_maps (page translation)
        k_refs = refs[:P]
        v_refs = refs[P:2 * P]
        if quant:
            ks_refs = refs[2 * P:3 * P]
            vs_refs = refs[3 * P:4 * P]
            ck_ref, cv_ref, o_ref, m_scr, l_scr, acc_scr = refs[4 * P:]
        else:
            ks_refs = vs_refs = None
            ck_ref, cv_ref, o_ref, m_scr, l_scr, acc_scr = refs[2 * P:]

        b = pl.program_id(0)
        qi = pl.program_id(2)
        t = pl.program_id(3)
        start = st_ref[b]   # tokens already resident in pages
        vd = vd_ref[b]      # real tokens in this row's chunk

        @pl.when(t == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        tile_rows = P * block_size
        k_start = t * tile_rows
        # whole q tiles past the row's live chunk skip compute (their
        # output rows are garbage the caller ignores; finalize emits the
        # zero-initialized scratch)
        q_live = qi * q_tile < vd

        def q2():
            return q_ref[0, 0].astype(jnp.float32).reshape(rows_q, -1)

        @pl.when(jnp.logical_and(jnp.logical_and(t < nt, k_start < start),
                                 q_live))
        def _prefix():
            k, v = _assemble_kv_tile(k_refs, v_refs, ks_refs, vs_refs, P)
            s = jax.lax.dot_general(q2(), k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            # prefix position of column r: k_start + r; live iff < start.
            # Chunk queries all sit at absolute positions >= start, so the
            # causal constraint is implied — only liveness is masked.
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < start, s, NEG_INF)
            _online_softmax_update(s, v, m_scr, l_scr, acc_scr)

        @pl.when(jnp.logical_and(t == nt, q_live))
        def _chunk():
            k = ck_ref[0, 0].astype(jnp.float32)             # (C, D)
            v = cv_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(q2(), k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            shape = (rows_q, chunk_len)
            c_idx = qi * q_tile + jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, shape, 0), q_tile)
            j_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mask = jnp.logical_and(j_idx <= c_idx, j_idx < vd)
            s = jnp.where(mask, s, NEG_INF)
            _online_softmax_update(s, v, m_scr, l_scr, acc_scr)

        @pl.when(t == nt)
        def _finalize():
            denom = jnp.maximum(l_scr[...], 1e-20)
            o_ref[0, 0] = (acc_scr[...] / denom[:, None]) \
                .reshape(group, q_tile, -1).astype(o_ref.dtype)

    return kernel


def _prefill_call(q, k_pages, v_pages, chunk_k, chunk_v, block_table,
                  starts, valid, scale_pages, *, pages_per_tile, q_tile,
                  interpret):
    """Shared pallas_call builder for the float / int8 twins
    (``scale_pages`` is None or the (k_scale, v_scale) pair)."""
    B, H, C, D = q.shape
    N, KVH, bs, _ = k_pages.shape
    nb = block_table.shape[1]
    assert nb >= 1, "block table must cover at least one logical block"
    assert H % KVH == 0
    group = H // KVH
    quant = scale_pages is not None
    scale = 1.0 / math.sqrt(D)

    P = pages_per_tile or auto_pages_per_tile(bs, nb)
    P = max(1, min(P, nb))
    nt = -(-nb // P)                 # prefix tiles; final grid step = chunk
    W = nt * P
    bt = _pad_block_table(block_table, N, W)
    Q = q_tile or auto_q_tile(C)
    Q = max(1, min(Q, C))
    if C % Q:
        Q = C                        # ragged chunk lengths keep one tile
    nq = C // Q
    # the GQA group's chunk queries ride in (group, Q, D) tiles (decode-
    # kernel pattern): pages are fetched once per KV head per q tile, not
    # once per q head
    qg = q.reshape(B, KVH, group, C, D)

    def _q_idx(b, h, qi, t, bt_ref, st_ref, vd_ref):
        return (b, h, 0, qi, 0)

    def _page_idx(b, h, qi, t, bt_ref, st_ref, vd_ref, *, p):
        # logical block t*P+p of sequence b -> physical page; blocks past
        # the live prefix (dead tiles AND the chunk grid step t == nt)
        # clamp to the last live block so their index never changes and
        # the pipeline skips the dead DMAs
        idx = _live_block_index(t * P + p, st_ref[b], bs, W)
        return (bt_ref[b, idx], h, 0, 0)

    def _scale_idx(b, h, qi, t, bt_ref, st_ref, vd_ref, *, p):
        idx = _live_block_index(t * P + p, st_ref[b], bs, W)
        return (bt_ref[b, idx], h, 0)

    def _chunk_idx(b, h, qi, t, bt_ref, st_ref, vd_ref):
        return (b, h, 0, 0)

    page_spec = lambda p: pl.BlockSpec(  # noqa: E731
        (1, 1, bs, D), functools.partial(_page_idx, p=p))
    in_specs = [pl.BlockSpec((1, 1, group, Q, D), _q_idx)]
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
    in_specs += [pl.BlockSpec((1, 1, C, D), _chunk_idx),
                 pl.BlockSpec((1, 1, C, D), _chunk_idx)]
    inputs += [chunk_k, chunk_v]

    kernel = _make_prefill_kernel(P=P, nt=nt, scale=scale, block_size=bs,
                                  chunk_len=C, q_tile=Q, group=group,
                                  quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block table + starts + valid, in SMEM
        grid=(B, KVH, nq, nt + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group, Q, D), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((group * Q,), jnp.float32),
            pltpu.VMEM((group * Q,), jnp.float32),
            pltpu.VMEM((group * Q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, group, C, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=("paged_prefill_attention_quant" if quant
              else "paged_prefill_attention"),
    )(bt, starts.astype(jnp.int32), valid.astype(jnp.int32), *inputs)
    return out.reshape(B, H, C, D)


def paged_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, chunk_k: jax.Array,
                            chunk_v: jax.Array, block_table: jax.Array,
                            starts: jax.Array, valid: jax.Array, *,
                            pages_per_tile: int | None = None,
                            q_tile: int | None = None,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, C, D); k_pages/v_pages: (N, KVH, bs, D); chunk_k/chunk_v:
    (B, KVH, C, D); block_table: (B, nb); starts/valid: (B,).  Returns
    (B, H, C, D) — rows past ``valid[b]`` (and rows of ``valid == 0``
    sequences) are garbage the caller must ignore, exactly like the gather
    fallback.  ``pages_per_tile=None`` auto-derives the kv-tile width from
    ``block_size`` (``auto_pages_per_tile``); ``q_tile=None`` auto-derives
    the query-tile height from the chunk length (``auto_q_tile`` — chunks
    past 128 queries split across grid steps instead of one VMEM tile)."""
    return _prefill_call(q, k_pages, v_pages, chunk_k, chunk_v, block_table,
                         starts, valid, None, pages_per_tile=pages_per_tile,
                         q_tile=q_tile, interpret=interpret)


def paged_prefill_attention_quant(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array,
                                  k_scale_pages: jax.Array,
                                  v_scale_pages: jax.Array,
                                  chunk_k: jax.Array, chunk_v: jax.Array,
                                  block_table: jax.Array, starts: jax.Array,
                                  valid: jax.Array, *,
                                  pages_per_tile: int | None = None,
                                  q_tile: int | None = None,
                                  interpret: bool = False) -> jax.Array:
    """int8 page pool twin: k/v pages int8 with per-row scale pages
    (N, KVH, bs); the prefix dequantizes in VMEM while the in-chunk
    keys/values stay float (they are fresh projections — same contract as
    the gather fallback).  Same ``pages_per_tile`` / ``q_tile`` tiling."""
    return _prefill_call(q, k_pages, v_pages, chunk_k, chunk_v, block_table,
                         starts, valid, (k_scale_pages, v_scale_pages),
                         pages_per_tile=pages_per_tile, q_tile=q_tile,
                         interpret=interpret)
