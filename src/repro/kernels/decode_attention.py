"""Pallas TPU decode-attention kernel (one query token vs KV cache).

The serving hot loop: for each sequence in the continuous batch, attend its
single new query against ``lengths[b]`` cached tokens.  Grid
(batch, kv_heads, num_kv_blocks); the whole GQA head-group's queries
(group, D) ride along in one tile so each KV block is streamed HBM→VMEM
exactly once per group (decode is memory-bound — KV traffic IS the roofline
term, see EXPERIMENTS.md §Roofline).

Per-sequence ``lengths`` masking supports ragged continuous batches; blocks
entirely past ``lengths[b]`` skip compute via ``pl.when``.

Length convention (shared by BOTH the float and the int8 kernel, and by the
paged variants in ``paged_decode_attention.py``): ``lengths[b]`` counts
every valid cache slot INCLUDING the token written this decode step — the
caller writes the new token's k/v at slot ``pos`` and passes ``pos + 1``.
``attend_decode`` computes this once (``kv_valid``) and feeds every backend
from it, so the quant / non-quant / paged paths cannot drift apart.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                   scale: float, block_k: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    b = pl.program_id(0)
    length = len_ref[b]  # tokens valid in this sequence's cache (incl. new one)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # (group, d)
        k = k_ref[0, 0].astype(jnp.float32)      # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def _decode_quant_kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float, block_k: int):
    """int8-KV variant: dequantize per-row inside VMEM (the HBM read is the
    int8 payload + scales — the roofline memory term halves; §Perf H3)."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    b = pl.program_id(0)
    length = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        ks = ks_ref[0, 0].astype(jnp.float32)       # (bk,)
        vs = vs_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32) * ks[:, None]
        v = v_ref[0, 0].astype(jnp.float32) * vs[:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention_quant(q: jax.Array, k: jax.Array, v: jax.Array,
                           k_scale: jax.Array, v_scale: jax.Array,
                           lengths: jax.Array, *,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k/v int8 (B, KVH, S, D); scales (B, KVH, S).

    ``lengths`` uses the same inclusive convention as ``decode_attention``:
    it COUNTS the newest token (whose k/v sits at slot ``lengths - 1``).
    """
    B, H, D = q.shape
    KVH, S = k.shape[1], k.shape[2]
    assert H % KVH == 0
    group = H // KVH
    scale = 1.0 / math.sqrt(D)

    block_k = min(block_k, max(S, 8))
    pad_k = (-S) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, pad_k)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, pad_k)))
    nk = k.shape[2] // block_k
    qg = q.reshape(B, KVH, group, D)

    kernel = functools.partial(_decode_quant_kernel, scale=scale, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=(B, KVH, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, group, D), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, ki: (b, h, ki)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, ki: (b, h, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, D), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KVH, group, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v, k_scale, v_scale)
    return out.reshape(B, H, D)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *,
                     block_k: int = DEFAULT_BLOCK_K,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D) single query per sequence; k/v: (B, KVH, S, D);
    lengths: (B,) int32 — number of valid cache slots (the new token's k/v
    must already be written at slot lengths-1... i.e. lengths INCLUDES it).
    Returns (B, H, D).
    """
    B, H, D = q.shape
    KVH, S = k.shape[1], k.shape[2]
    assert H % KVH == 0
    group = H // KVH
    scale = 1.0 / math.sqrt(D)

    block_k = min(block_k, max(S, 8))
    pad_k = (-S) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nk = k.shape[2] // block_k

    # (B, KVH, group, D) query layout: one tile per (b, kv-head)
    qg = q.reshape(B, KVH, group, D)
    lengths = lengths.astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)

    out = pl.pallas_call(
        kernel,
        grid=(B, KVH, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths, prefetched whole
            pl.BlockSpec((1, 1, group, D), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, D), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KVH, group, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, H, D)
