"""GQA attention with full / sliding-window variants and KV-cache paths.

Three entry points per block:
  * ``attend_train``   — full-sequence causal attention (no cache).
  * ``attend_prefill`` — like train, but also returns the populated cache.
  * ``attend_decode``  — one query token against the cache (per-sequence
                         lengths; continuous batching friendly).

The dense KV cache is a dict ``{"k": (B, KVH, S, D), "v": (B, KVH, S, D)}``
plus per-sequence ``lengths`` carried by the caller.  Sliding-window models
keep a rolling cache of size ``window`` (write index = pos % window), so
the ``long_500k`` shape materializes only O(window) memory.

The paged serving twins (``attend_decode_paged`` /
``attend_prefill_chunk_paged``) replace the per-slot arrays with a global
page pool ``{"k": (num_blocks, KVH, block_size, D), ...}`` addressed
through per-sequence block tables (full attention only — see
``init_paged_kv_cache``).

Backend support matrix (``EngineConfig.attention_backend`` selects the
column; every cell is token-identical to ``xla``):

  capability           xla    pallas  paged-xla  paged-pallas
  chunked prefill      yes    yes(*)  yes        yes (fused kernel)
  paged KV pool        no     no      yes        yes (block-table kernels)
  int8 KV (kv_quant)   yes    yes     yes        yes (fused dequant)
  sliding window       yes    partial no         no
  decode kernel        jnp    Pallas  gather     Pallas multi-page tiles

  (*) "pallas" accelerates train/prefill (flash) and dense decode; the
  chunked-prefill chunk step itself uses the jnp two-segment path, and
  rolling SWA decode always falls back to jnp slot-validity masking.
  Paged backends require full attention + chunked prefill (engine gates).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers


def init_attention(key, cfg, dtype=jnp.float32):
    """cfg: ModelConfig (uses num_heads/num_kv_heads/head_dim/qkv_bias)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(kq, d, cfg.num_heads * hd, dtype),
        "wk": layers.dense_init(kk, d, cfg.num_kv_heads * hd, dtype),
        "wv": layers.dense_init(kv, d, cfg.num_kv_heads * hd, dtype),
        "wo": layers.dense_init(ko, cfg.num_heads * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


def _project_qkv(params, cfg, x, positions):
    """x: (B, L, d) -> q (B, L, H, hd), k/v (B, L, KVH, hd), with RoPE."""
    B, L, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, L, cfg.num_heads, hd)
    k = k.reshape(B, L, cfg.num_kv_heads, hd)
    v = v.reshape(B, L, cfg.num_kv_heads, hd)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
          mask: Optional[jax.Array]) -> jax.Array:
    """q: (B, H, Lq, D), k/v: (B, KVH, Lkv, D), GQA by head-group reshape.

    mask: broadcastable to (B, 1, Lq, Lkv), True = attend.

    Perf note (EXPERIMENTS §Perf H3): operands stay in their storage dtype
    (bf16 on TPU) and accumulation happens in f32 via
    ``preferred_element_type`` — materializing ``.astype(f32)`` copies of
    q/k/v doubled the decode path's HBM traffic (the KV cache is the
    memory-roofline term for decode).
    """
    B, H, Lq, D = q.shape
    KVH = k.shape[1]
    group = H // KVH
    q = q.reshape(B, KVH, group, Lq, D)
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bkgqd,bksd->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask,
                           scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, Lq, D).astype(v.dtype)


def _sdpa_q_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, window, chunk: int) -> jax.Array:
    """Query-chunked exact attention (flash-style memory behaviour at the
    XLA level, EXPERIMENTS §Perf H1): peak score tensor is
    (B, KVH, group, chunk, Lkv) instead of (B, KVH, group, L, L).
    ``lax.map`` serializes chunks, so only one tile is live at a time."""
    B, H, L, D = q.shape
    Lkv = k.shape[2]
    assert L % chunk == 0, (L, chunk)
    nq = L // chunk

    def one(qi):
        q_off = qi * chunk
        qs = jax.lax.dynamic_slice_in_dim(q, q_off, chunk, axis=2)
        if window is not None:
            mask = layers.sliding_window_mask(chunk, Lkv, q_off, window)[None, None]
        elif causal:
            mask = layers.causal_mask(chunk, Lkv, q_off)[None, None]
        else:
            mask = None
        return _sdpa(qs, k, v, mask)  # (B, H, chunk, D)

    out = jax.lax.map(one, jnp.arange(nq))  # (nq, B, H, chunk, D)
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, L, D)


def attend_train(params, cfg, x: jax.Array, positions: jax.Array,
                 *, bidirectional: bool = False) -> jax.Array:
    """Full-sequence attention. x: (B, L, d)."""
    B, L, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    q = q.transpose(0, 2, 1, 3)  # (B, H, L, D)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    chunk = cfg.train_attn_chunk
    if cfg.use_pallas_attention and not bidirectional:
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.flash_attention(q, k, v, causal=True,
                                         window=cfg.sliding_window)
    elif chunk is not None and not bidirectional and L % chunk == 0 and L > chunk:
        out = _sdpa_q_chunked(q, k, v, causal=True,
                              window=cfg.sliding_window, chunk=chunk)
    else:
        if bidirectional:
            mask = None
        elif cfg.sliding_window is not None:
            mask = layers.sliding_window_mask(L, L, 0, cfg.sliding_window)[None, None]
        else:
            mask = layers.causal_mask(L, L, 0)[None, None]
        out = _sdpa(q, k, v, mask)
    out = out.transpose(0, 2, 1, 3).reshape(B, L, cfg.num_heads * cfg.resolved_head_dim)
    return out @ params["wo"]


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def cache_len(cfg, max_seq: int) -> int:
    """Materialized cache length: rolling window for SWA, else max_seq."""
    if cfg.sliding_window is not None:
        return min(max_seq, cfg.sliding_window)
    return max_seq


def init_kv_cache(cfg, batch: int, max_seq: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    S = cache_len(cfg, max_seq)
    hd = cfg.resolved_head_dim
    shape = (batch, cfg.num_kv_heads, S, hd)
    if cfg.kv_quant:
        return {"k": jnp.zeros(shape, jnp.int8), "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], dtype),
                "v_scale": jnp.zeros(shape[:-1], dtype)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv_cache(cfg, num_blocks: int, block_size: int,
                        dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Global KV page pool (PagedAttention layout), one per layer.

    Unlike ``init_kv_cache`` there is NO per-slot batch axis: every sequence
    in the engine shares the pool and owns pages named by its
    ``BlockManager`` block table, so engine KV capacity is
    ``num_blocks * block_size`` tokens total rather than
    ``max_slots * max_seq_len``.  Logical position ``p`` of a sequence lives
    in page ``block_table[p // block_size]`` at row ``p % block_size``.
    """
    hd = cfg.resolved_head_dim
    shape = (num_blocks, cfg.num_kv_heads, block_size, hd)
    if cfg.kv_quant:
        return {"k": jnp.zeros(shape, jnp.int8), "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], dtype),
                "v_scale": jnp.zeros(shape[:-1], dtype)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (..., D) -> (int8 values, per-row scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(x.dtype)


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)


def attend_prefill(params, cfg, x: jax.Array, positions: jax.Array,
                   cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal attention over the prompt AND cache population.

    Assumes prefill starts at position 0 and ``positions`` are
    [0..L) per sequence (right-padded batches use the padding mask upstream
    via lengths in decode).  x: (B, L, d).
    """
    B, L, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)  # (B, KVH, L, D)
    vh = v.transpose(0, 2, 1, 3)
    if cfg.sliding_window is not None:
        mask = layers.sliding_window_mask(L, L, 0, cfg.sliding_window)[None, None]
    else:
        mask = layers.causal_mask(L, L, 0)[None, None]
    out = _sdpa(qh, kh, vh, mask)
    out = out.transpose(0, 2, 1, 3).reshape(B, L, cfg.num_heads * cfg.resolved_head_dim)

    S = cache["k"].shape[2]
    if cfg.sliding_window is not None and L > S:
        # keep only the last `window` tokens, aligned to rolling index
        # rolling write index after L tokens is L % S; we store the last S
        # tokens such that slot (p % S) holds position p.
        last = jnp.arange(L - S, L)
        slots = last % S
        kh_tail = kh[:, :, L - S:, :]
        vh_tail = vh[:, :, L - S:, :]
        if cfg.kv_quant:
            kq, ks = _quantize_kv(kh_tail)
            vq, vs = _quantize_kv(vh_tail)
            return out @ params["wo"], {
                "k": jnp.zeros_like(cache["k"]).at[:, :, slots, :].set(kq),
                "v": jnp.zeros_like(cache["v"]).at[:, :, slots, :].set(vq),
                "k_scale": jnp.zeros_like(cache["k_scale"]).at[:, :, slots].set(ks),
                "v_scale": jnp.zeros_like(cache["v_scale"]).at[:, :, slots].set(vs),
            }
        new_k = jnp.zeros_like(cache["k"]).at[:, :, slots, :].set(kh_tail)
        new_v = jnp.zeros_like(cache["v"]).at[:, :, slots, :].set(vh_tail)
    else:
        pad = S - L
        if cfg.kv_quant:
            kq, ks = _quantize_kv(kh)
            vq, vs = _quantize_kv(vh)
            if pad > 0:
                kq = jnp.pad(kq, ((0, 0), (0, 0), (0, pad), (0, 0)))
                vq = jnp.pad(vq, ((0, 0), (0, 0), (0, pad), (0, 0)))
                ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad)))
                vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad)))
            return out @ params["wo"], {"k": kq, "v": vq,
                                        "k_scale": ks, "v_scale": vs}
        new_k = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad > 0 else kh
        new_v = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad > 0 else vh
    return out @ params["wo"], {"k": new_k, "v": new_v}


def attend_prefill_chunk(params, cfg, x: jax.Array, positions: jax.Array,
                         valid: jax.Array,
                         cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunk-granular prefill continuation (chunked-prefill serving path).

    Attends this chunk's queries against the already-populated cache plus
    the chunk's own keys, and writes the chunk's k/v into the cache at their
    absolute positions (rolling slots for SWA).

    x: (B, C, d) right-padded chunk embeddings; positions: (B, C) absolute
    token positions (``starts[:, None] + arange(C)``); valid: (B,) number of
    real tokens in each row's chunk — 0 marks an inactive row whose writes
    are dropped and whose outputs the caller ignores.

    The attention is computed in two kv segments so a rolling SWA cache
    never reads a slot this same chunk just overwrote: the PRE-chunk cache
    (positions <= start-1) and the in-chunk keys (read from the fresh
    projections).

    Donation note: for FULL attention the pre-chunk segment reads the
    POST-write cache — the chunk writes land at slots >= start while the
    segment mask only passes slots < start, so the values are identical
    and the (donated) cache buffer has no consumer besides the in-place
    update, letting XLA skip the per-chunk pool copy.  Rolling SWA keeps
    the pre-write read (slot aliasing: this chunk may overwrite slots the
    mask still passes), which forces a copy when donated — correctness
    first.
    """
    B, C, _ = x.shape
    S = cache["k"].shape[2]
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(params, cfg, x, positions)  # k/v: (B, C, KVH, hd)
    starts = positions[:, 0]

    # ---- cache write: slot = pos (full) / pos % S (rolling SWA) ----------
    in_chunk = jnp.arange(C)[None, :] < valid[:, None]          # (B, C)
    slot = positions % S if cfg.sliding_window is not None else positions
    write_slot = jnp.where(in_chunk, slot, S)                    # S => dropped
    b_idx = jnp.arange(B)[:, None]
    if cfg.kv_quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache = {
            "k": cache["k"].at[b_idx, :, write_slot, :].set(kq, mode="drop"),
            "v": cache["v"].at[b_idx, :, write_slot, :].set(vq, mode="drop"),
            "k_scale": cache["k_scale"].at[b_idx, :, write_slot].set(ks, mode="drop"),
            "v_scale": cache["v_scale"].at[b_idx, :, write_slot].set(vs, mode="drop"),
        }
        read = cache if cfg.sliding_window is not None else new_cache
        old_k = _dequantize_kv(read["k"], read["k_scale"], x.dtype)
        old_v = _dequantize_kv(read["v"], read["v_scale"], x.dtype)
    else:
        new_cache = {
            "k": cache["k"].at[b_idx, :, write_slot, :].set(k, mode="drop"),
            "v": cache["v"].at[b_idx, :, write_slot, :].set(v, mode="drop"),
        }
        read = cache if cfg.sliding_window is not None else new_cache
        old_k, old_v = read["k"], read["v"]

    # ---- attention: [pre-chunk cache | in-chunk keys] --------------------
    qh = q.transpose(0, 2, 1, 3)                                 # (B, H, C, hd)
    kh = k.transpose(0, 2, 1, 3)                                 # (B, KVH, C, hd)
    vh = v.transpose(0, 2, 1, 3)
    k_all = jnp.concatenate([old_k, kh], axis=2)                 # (B, KVH, S+C, hd)
    v_all = jnp.concatenate([old_v, vh], axis=2)

    q_pos = positions[:, :, None]                                # (B, C, 1)
    s_idx = jnp.arange(S)[None, None, :]                         # (1, 1, S)
    if cfg.sliding_window is not None:
        # slot s of the PRE-chunk cache holds the largest position
        # p <= start-1 with p % S == s (negative => never written).
        prev = (starts - 1)[:, None, None]
        p_s = prev - ((prev - s_idx) % S)
        cache_mask = (p_s >= 0) & (p_s > q_pos - cfg.sliding_window)
    else:
        cache_mask = jnp.broadcast_to(s_idx < starts[:, None, None], (B, C, S))
    j_idx = jnp.arange(C)[None, None, :]
    p_j = starts[:, None, None] + j_idx
    chunk_mask = (p_j <= q_pos) & (j_idx < valid[:, None, None])
    if cfg.sliding_window is not None:
        chunk_mask = chunk_mask & (p_j > q_pos - cfg.sliding_window)
    mask = jnp.concatenate([cache_mask, chunk_mask], axis=-1)[:, None]

    out = _sdpa(qh, k_all, v_all, mask)
    out = out.transpose(0, 2, 1, 3).reshape(B, C, cfg.num_heads * hd)
    return out @ params["wo"], new_cache


def attend_decode(params, cfg, x: jax.Array, lengths: jax.Array,
                  cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: (B, 1, d); lengths: (B,) tokens already cached
    (i.e. the new token's absolute position).  Returns (out, new_cache).
    """
    B = x.shape[0]
    S = cache["k"].shape[2]
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(params, cfg, x, lengths[:, None])
    # write new k/v at slot (rolling for SWA)
    slot = lengths % S if cfg.sliding_window is not None else lengths
    k_new = k[:, 0]  # (B, KVH, D)
    v_new = v[:, 0]
    batch_idx = jnp.arange(B)
    new_cache = {}
    if cfg.kv_quant:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        new_cache = {
            "k": cache["k"].at[batch_idx, :, slot, :].set(kq),
            "v": cache["v"].at[batch_idx, :, slot, :].set(vq),
            "k_scale": cache["k_scale"].at[batch_idx, :, slot].set(ks),
            "v_scale": cache["v_scale"].at[batch_idx, :, slot].set(vs),
        }
        new_k = _dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        new_v = _dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        new_k = cache["k"].at[batch_idx, :, slot, :].set(k_new)
        new_v = cache["v"].at[batch_idx, :, slot, :].set(v_new)

    # ONE length convention for every decode backend: the cache now holds
    # kv_valid = lengths + 1 tokens (the new token's k/v was just written at
    # slot `lengths`), and the kernels/masks below all consume kv_valid.
    # The kernel-side contract (count INCLUDES the newest token) is
    # documented in kernels/decode_attention.py and locked in by the
    # quant-vs-float parity tests.
    kv_valid = lengths + 1

    # Pallas decode kernel path: blocked KV streaming, per-seq lengths
    # masking (incl. fused int8 dequant).  Rolling SWA caches keep the XLA
    # path (slot-validity masking is window-specific).
    if cfg.use_pallas_attention and cfg.sliding_window is None:
        from repro.kernels import ops as kernel_ops
        q1 = q[:, 0]  # (B, H, D)
        if cfg.kv_quant:
            attn = kernel_ops.decode_attention_quant(
                q1, new_cache["k"], new_cache["v"], new_cache["k_scale"],
                new_cache["v_scale"], kv_valid)
        else:
            attn = kernel_ops.decode_attention(q1, new_k, new_v, kv_valid)
        out = attn[:, None].reshape(B, 1, cfg.num_heads * hd)
        proj = out @ params["wo"]
        return (proj, new_cache) if cfg.kv_quant else (proj, {"k": new_k, "v": new_v})

    qh = q.transpose(0, 2, 1, 3)  # (B, H, 1, D)
    kv_pos = jnp.arange(S)[None, :]  # slot index
    if cfg.sliding_window is not None:
        # slot s holds absolute position p iff p % S == s and p <= length;
        # valid iff within the last `window` positions.
        # absolute position held in slot s: the largest p <= lengths with p%S==s
        abs_pos = lengths[:, None] - ((lengths[:, None] - kv_pos) % S)
        valid = (abs_pos >= 0) & (abs_pos >= lengths[:, None] - (S - 1))
        mask = valid[:, None, None, :]  # (B,1,1,S)
    else:
        mask = (kv_pos < kv_valid[:, None])[:, None, None, :]
    out = _sdpa(qh, new_k, new_v, mask)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, cfg.num_heads * hd)
    if cfg.kv_quant:
        return out @ params["wo"], new_cache
    return out @ params["wo"], {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# paged KV (block-table) serving paths — full attention only
# ---------------------------------------------------------------------------

def _paged_dims(cache: Dict[str, jax.Array]) -> Tuple[int, int]:
    """(num_blocks, block_size) of a page-pool cache layer."""
    return cache["k"].shape[0], cache["k"].shape[2]


def _write_pages(cfg, cache: Dict[str, jax.Array], k: jax.Array,
                 v: jax.Array, page: jax.Array,
                 offset: jax.Array) -> Dict[str, jax.Array]:
    """Scatter per-token k/v (..., KVH, D) into pages at (page, offset).

    ``page``/``offset`` index arrays share the leading dims of k/v; sentinel
    page ids (>= num_blocks) drop the write (inactive batch rows, logical
    blocks not yet allocated).
    """
    if cfg.kv_quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return {
            "k": cache["k"].at[page, :, offset, :].set(kq, mode="drop"),
            "v": cache["v"].at[page, :, offset, :].set(vq, mode="drop"),
            "k_scale": cache["k_scale"].at[page, :, offset].set(ks, mode="drop"),
            "v_scale": cache["v_scale"].at[page, :, offset].set(vs, mode="drop"),
        }
    return {
        "k": cache["k"].at[page, :, offset, :].set(k, mode="drop"),
        "v": cache["v"].at[page, :, offset, :].set(v, mode="drop"),
    }


def _gather_dense_kv(cfg, cache: Dict[str, jax.Array], block_table: jax.Array,
                     dtype) -> Tuple[jax.Array, jax.Array]:
    """Densify a page pool through block tables -> (B, KVH, nb*bs, D) k/v
    (dequantized for int8 pools).  The XLA reference path on CPU; positions
    past each sequence's length hold garbage the caller must mask.

    k and v (and the scale pair on the quant path) ride ONE stacked gather
    each (``gather_kv_pages_fused``) — two gathers total instead of four
    for int8 pools, one instead of two for float."""
    from repro.kernels.paged_decode_attention import gather_kv_pages_fused
    k, v = gather_kv_pages_fused(cache["k"], cache["v"], block_table)
    if cfg.kv_quant:
        ks, vs = gather_kv_pages_fused(cache["k_scale"], cache["v_scale"],
                                       block_table)
        k = _dequantize_kv(k, ks, dtype)
        v = _dequantize_kv(v, vs, dtype)
    return k, v


def attend_decode_paged(params, cfg, x: jax.Array, lengths: jax.Array,
                        block_table: jax.Array,
                        cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against the paged KV pool.

    x: (B, 1, d); lengths: (B,) tokens already cached (= the new token's
    absolute position); block_table: (B, nb) physical page ids, sentinel
    entries >= num_blocks marking unallocated logical blocks; cache: page
    pool from ``init_paged_kv_cache``.  Requires full attention
    (``cfg.sliding_window is None`` — rolling-window paging is a ROADMAP
    follow-on).

    The new token's k/v is scattered into page ``block_table[b, pos // bs]``
    row ``pos % bs``; rows whose write page is unallocated (inactive slots,
    mid-prefill rows at a block boundary) drop the write via the sentinel.
    """
    B = x.shape[0]
    num_blocks, bs = _paged_dims(cache)
    nb = block_table.shape[1]
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(params, cfg, x, lengths[:, None])
    k_new = k[:, 0]  # (B, KVH, D)
    v_new = v[:, 0]

    logical = lengths // bs
    offset = lengths % bs
    page = jnp.take_along_axis(
        block_table, jnp.minimum(logical, nb - 1)[:, None], axis=1)[:, 0]
    page = jnp.where(logical < nb, page, num_blocks)  # sentinel => dropped
    new_cache = _write_pages(cfg, cache, k_new, v_new, page, offset)

    # same inclusive convention as the dense path: the pool now holds
    # kv_valid tokens for each row, newest at logical position `lengths`
    kv_valid = lengths + 1
    q1 = q[:, 0]  # (B, H, D)
    if cfg.use_pallas_attention:
        from repro.kernels import ops as kernel_ops
        if cfg.kv_quant:
            attn = kernel_ops.paged_decode_attention_quant(
                q1, new_cache["k"], new_cache["v"], new_cache["k_scale"],
                new_cache["v_scale"], block_table, kv_valid,
                pages_per_tile=cfg.paged_pages_per_tile)
        else:
            attn = kernel_ops.paged_decode_attention(
                q1, new_cache["k"], new_cache["v"], block_table, kv_valid,
                pages_per_tile=cfg.paged_pages_per_tile)
    else:
        k_dense, v_dense = _gather_dense_kv(cfg, new_cache, block_table, x.dtype)
        mask = (jnp.arange(nb * bs)[None, :] < kv_valid[:, None])[:, None, None, :]
        attn = _sdpa(q.transpose(0, 2, 1, 3), k_dense, v_dense, mask)[:, :, 0]
    out = attn[:, None].reshape(B, 1, cfg.num_heads * hd)
    return out @ params["wo"], new_cache


def attend_prefill_chunk_paged(params, cfg, x: jax.Array,
                               positions: jax.Array, valid: jax.Array,
                               block_table: jax.Array,
                               cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunk-granular prefill continuation writing into the paged KV pool.

    Same contract as ``attend_prefill_chunk`` (x: (B, C, d) right-padded
    chunk, positions absolute, valid: (B,) real tokens per row, 0 =
    inactive) except the chunk's k/v scatter to (page, offset) pairs named
    by ``block_table`` instead of per-slot dense rows.  Full attention only.

    With ``cfg.use_pallas_attention`` the attention runs the flash-style
    paged prefill-chunk kernel: KV pages stream in place through the
    SMEM-prefetched block table and an online softmax folds the
    page-resident prefix with the causal in-chunk segment — per-chunk HBM
    reads proportional to live tokens, no densified copy.  The XLA
    fallback densifies the PRE-chunk pages with one stacked gather and
    appends the in-chunk keys, exactly mirroring the dense chunk path's
    two-segment masking (the CPU oracle the kernel is parity-tested
    against).

    Donation note: both the kernel and the gather fallback read the
    POST-write pool.  The chunk's page writes land at logical positions
    >= start while the prefix segment masks to positions < start, so the
    attended values are identical to a pre-write read — and the donated
    pool buffer's only consumer is the in-place scatter, so XLA updates
    the pages without copying the pool each chunk.
    """
    B, C, _ = x.shape
    num_blocks, bs = _paged_dims(cache)
    nb = block_table.shape[1]
    S = nb * bs
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(params, cfg, x, positions)  # k/v: (B, C, KVH, hd)
    starts = positions[:, 0]

    # ---- page writes: token (b, j) -> page bt[b, pos//bs], row pos%bs ----
    in_chunk = jnp.arange(C)[None, :] < valid[:, None]           # (B, C)
    logical = positions // bs
    offset = positions % bs
    page = jnp.take_along_axis(block_table, jnp.clip(logical, 0, nb - 1), axis=1)
    page = jnp.where(in_chunk & (logical < nb), page, num_blocks)
    new_cache = _write_pages(cfg, cache, k, v, page, offset)

    # ---- attention: [pre-chunk pages | in-chunk keys] --------------------
    qh = q.transpose(0, 2, 1, 3)                                 # (B, H, C, hd)
    kh = k.transpose(0, 2, 1, 3)                                 # (B, KVH, C, hd)
    vh = v.transpose(0, 2, 1, 3)
    starts_i = starts.astype(jnp.int32)
    valid_i = valid.astype(jnp.int32)

    if cfg.use_pallas_attention:
        # fused kernel: prefix pages stream in place from the POST-write
        # pool (rows >= start are masked — see the donation note above),
        # in-chunk k/v stay float
        from repro.kernels import ops as kernel_ops
        if cfg.kv_quant:
            attn = kernel_ops.paged_prefill_attention_quant(
                qh, new_cache["k"], new_cache["v"], new_cache["k_scale"],
                new_cache["v_scale"], kh, vh, block_table, starts_i, valid_i,
                pages_per_tile=cfg.paged_pages_per_tile)
        else:
            attn = kernel_ops.paged_prefill_attention(
                qh, new_cache["k"], new_cache["v"], kh, vh, block_table,
                starts_i, valid_i, pages_per_tile=cfg.paged_pages_per_tile)
        out = attn.transpose(0, 2, 1, 3).reshape(B, C, cfg.num_heads * hd)
        return out @ params["wo"], new_cache

    old_k, old_v = _gather_dense_kv(cfg, new_cache, block_table, x.dtype)
    k_all = jnp.concatenate([old_k, kh], axis=2)                 # (B, KVH, S+C, hd)
    v_all = jnp.concatenate([old_v, vh], axis=2)

    q_pos = positions[:, :, None]                                # (B, C, 1)
    s_idx = jnp.arange(S)[None, None, :]                         # (1, 1, S)
    cache_mask = jnp.broadcast_to(s_idx < starts[:, None, None], (B, C, S))
    j_idx = jnp.arange(C)[None, None, :]
    p_j = starts[:, None, None] + j_idx
    chunk_mask = (p_j <= q_pos) & (j_idx < valid[:, None, None])
    mask = jnp.concatenate([cache_mask, chunk_mask], axis=-1)[:, None]

    out = _sdpa(qh, k_all, v_all, mask)
    out = out.transpose(0, 2, 1, 3).reshape(B, C, cfg.num_heads * hd)
    return out @ params["wo"], new_cache


def attention_param_axes(cfg):
    """Logical sharding axes per leaf (mirrors init_attention)."""
    p = {
        "wq": ("embed", "heads_x_dim"),
        "wk": ("embed", "kv_heads_x_dim"),
        "wv": ("embed", "kv_heads_x_dim"),
        "wo": ("heads_x_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = ("heads_x_dim",)
        p["bk"] = ("kv_heads_x_dim",)
        p["bv"] = ("kv_heads_x_dim",)
    return p
