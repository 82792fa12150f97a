"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

One kernel computes the FULL scan for a (batch, head) slice: grid
(batch, heads, num_chunks) with the chunk dimension innermost and
"arbitrary" semantics — the inter-chunk state (N, P) is carried in VMEM
scratch across sequential grid steps, so the recurrence never round-trips
to HBM (the GPU implementation's inter-kernel state materialization is
exactly what we avoid; DESIGN.md §3).

Per chunk of length Q:
    y[i] = Σ_{j<=i} (C_i·B_j) exp(cum_i − cum_j) dt_j x_j   (intra, MXU)
         + C_i exp(cum_i) · h                               (inter)
    h'   = exp(cum_Q) h + Σ_j exp(cum_Q − cum_j) dt_j B_j ⊗ x_j

Tiles: x (Q, P), B/C (Q, N), dt (Q,) with Q=chunk_size (default 64),
N=d_state, P=head_dim — all ≤ (128, 128) f32 ⇒ < 1 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, h_scr, *,
                chunk: int):
    ci = pl.program_id(2)
    h = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # (Q,)
    B = b_ref[0, 0, 0].astype(jnp.float32)        # (Q, N)
    C = c_ref[0, 0, 0].astype(jnp.float32)        # (Q, N)
    A = a_ref[h]                               # scalar (negative)

    log_a = dt * A                             # (Q,)
    cum = jnp.cumsum(log_a)                    # inclusive

    # intra-chunk quadratic form
    seg = cum[:, None] - cum[None, :]          # (Q, Q)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(iota_j <= iota_i, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    att = cb * decay * dt[None, :]
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)

    # inter-chunk contribution from carried state
    h_prev = h_scr[...]                        # (N, P)
    c_in = C * jnp.exp(cum)[:, None]
    y = y + jax.lax.dot_general(c_in, h_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update
    decay_to_end = jnp.exp(cum[-1] - cum)      # (Q,)
    bw = B * (dt * decay_to_end)[:, None]      # (Q, N)
    new_state = jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    h_scr[...] = jnp.exp(cum[-1]) * h_prev + new_state

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, chunk: int, *, interpret: bool = False) -> jax.Array:
    """x: (B, L, H, P); dt: (B, L, H); A: (H,); Bm/Cm: (B, L, G, N).
    Returns y (B, L, H, P).  L % chunk == 0 required.
    """
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert L % chunk == 0
    nc = L // chunk
    rep = H // G

    # head-major chunked layouts
    xh = x.transpose(0, 2, 1, 3).reshape(Bsz, H, nc, chunk, P)
    dth = dt.transpose(0, 2, 1).reshape(Bsz, H, nc, chunk)
    Bh = Bm.transpose(0, 2, 1, 3).reshape(Bsz, G, nc, chunk, N)
    Ch = Cm.transpose(0, 2, 1, 3).reshape(Bsz, G, nc, chunk, N)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)

    out = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # A, whole (H,)
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, ci: (b, h // rep, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, ci: (b, h // rep, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, nc, chunk, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth, Bh, Ch)
    return out.reshape(Bsz, H, L, P).transpose(0, 2, 1, 3)
