"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) resolves through ``default_interpret``,
the one place that chooses Pallas interpret mode: kernels compile to
Mosaic when JAX's default backend is a TPU and are interpreted (the
kernel body run as plain HLO, correct but slow) everywhere else.  Model
code calls the kernels only through these wrappers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (
    decode_attention as _decode_attention,
    decode_attention_quant as _decode_attention_quant,
)
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.paged_decode_attention import (
    paged_decode_attention as _paged_decode_attention,
    paged_decode_attention_quant as _paged_decode_attention_quant,
)
from repro.kernels.paged_prefill_attention import (
    paged_prefill_attention as _paged_prefill_attention,
    paged_prefill_attention_quant as _paged_prefill_attention_quant,
)
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan


def default_interpret() -> bool:
    """Interpret Pallas kernels unless JAX's default backend is a TPU.

    Read at trace time: a program traced on a CPU host carries
    interpreted kernels even when it is compiled for a described TPU."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return _flash_attention(q, k, v, causal=causal, window=window,
                            block_q=block_q, block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, block_k: int = 256,
                     interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return _decode_attention(q, k, v, lengths, block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_quant(q, k, v, k_scale, v_scale, lengths, *,
                           block_k: int = 256, interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return _decode_attention_quant(q, k, v, k_scale, v_scale, lengths,
                                   block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("pages_per_tile", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_table, lengths, *,
                           pages_per_tile: int | None = None,
                           interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return _paged_decode_attention(q, k_pages, v_pages, block_table, lengths,
                                   pages_per_tile=pages_per_tile,
                                   interpret=interp)


@functools.partial(jax.jit, static_argnames=("pages_per_tile", "interpret"))
def paged_decode_attention_quant(q, k_pages, v_pages, k_scale_pages,
                                 v_scale_pages, block_table, lengths, *,
                                 pages_per_tile: int | None = None,
                                 interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return _paged_decode_attention_quant(q, k_pages, v_pages, k_scale_pages,
                                         v_scale_pages, block_table, lengths,
                                         pages_per_tile=pages_per_tile,
                                         interpret=interp)


@functools.partial(jax.jit, static_argnames=("pages_per_tile", "q_tile",
                                             "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, chunk_k, chunk_v,
                            block_table, starts, valid, *,
                            pages_per_tile: int | None = None,
                            q_tile: int | None = None,
                            interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return _paged_prefill_attention(q, k_pages, v_pages, chunk_k, chunk_v,
                                    block_table, starts, valid,
                                    pages_per_tile=pages_per_tile,
                                    q_tile=q_tile, interpret=interp)


@functools.partial(jax.jit, static_argnames=("pages_per_tile", "q_tile",
                                             "interpret"))
def paged_prefill_attention_quant(q, k_pages, v_pages, k_scale_pages,
                                  v_scale_pages, chunk_k, chunk_v,
                                  block_table, starts, valid, *,
                                  pages_per_tile: int | None = None,
                                  q_tile: int | None = None,
                                  interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return _paged_prefill_attention_quant(q, k_pages, v_pages, k_scale_pages,
                                          v_scale_pages, chunk_k, chunk_v,
                                          block_table, starts, valid,
                                          pages_per_tile=pages_per_tile,
                                          q_tile=q_tile, interpret=interp)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 64, interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret
    return _ssd_scan(x, dt, A, Bm, Cm, chunk, interpret=interp)
