"""Pallas TPU flash-attention (prefill) kernel.

Online-softmax tiling: grid (batch, q_heads, num_q_blocks, num_kv_blocks)
with the kv dimension innermost; running max / denominator / accumulator
live in VMEM scratch and persist across the kv grid steps (TPU grid
iteration is sequential).  GQA is handled in the k/v ``index_map`` (query
head h reads kv head ``h // group``) so kv tiles are fetched once per
group without materializing repeated heads in HBM.

Tiles default to (128, head_dim): MXU-aligned (multiples of 8×128 lanes)
and well under the ~16 MiB/core VMEM budget:
  q (128, D) + k (128, D) + v (128, D) + acc (128, D) @ f32 ≈ 256 KiB for D=128.

Causal / sliding-window masking is applied per-tile; fully-masked tiles
skip the matmul via ``pl.when``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, seq_q: int,
                  seq_kv: int, causal: bool, window):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def needed():
        if not causal:
            live = True
        else:
            live = k_start <= q_start + block_q - 1  # any kv pos <= any q pos
        if window is not None:
            live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)
        return live

    @pl.when(needed() if (causal or window is not None) else True)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_kv  # kv padding
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, Lq, D); k/v: (B, KVH, Lkv, D) -> (B, H, Lq, D).

    Lq / Lkv are padded to tile multiples internally; padded kv positions are
    masked, padded q rows are sliced off.
    """
    B, H, Lq, D = q.shape
    KVH, Lkv = k.shape[1], k.shape[2]
    assert H % KVH == 0
    group = H // KVH
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, max(Lq, 8))
    block_k = min(block_k, max(Lkv, 8))
    pad_q = (-Lq) % block_q
    pad_k = (-Lkv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=Lq, seq_kv=Lkv, causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Lq, :]
