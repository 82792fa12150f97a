"""Work counts from live lengths, and the chip's peaks.

Operations and bytes are those the algorithm needs for the tokens that
were actually computed, at the context each of them saw: never the padded
grid of a dispatch, nor how a kernel happens to be written.  A kernel
that is reimplemented is then read against the same work.

The attention counts are per layer and per call of the kernel; a model
step calls each kernel once per layer.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Iterable, Tuple

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"
BF16 = 2  # bytes


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(table)}")
    return table[device_kind]


def decode_attention(heads: int, kv_heads: int, head_dim: int,
                     contexts: Iterable[int]) -> Tuple[float, float]:
    """One layer of the paged decode kernel: each new token's query
    against ``ctx`` cached keys (its own included).  FLOPs: QK^T and PV,
    2 * heads * head_dim * ctx each.  Bytes: K and V of the context read
    once per KV head, the query read and the output written."""
    flops = bytes_ = 0.0
    for ctx in contexts:
        flops += 4.0 * heads * head_dim * ctx
        bytes_ += BF16 * (2 * kv_heads * head_dim * ctx
                          + 2 * heads * head_dim)
    return flops, bytes_


def prefill_attention(heads: int, kv_heads: int, head_dim: int,
                      spans: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    """One layer of the paged prefill-chunk kernel over chunks
    ``[start, end)``: the token at position p attends p + 1 keys (causal,
    itself included).  Bytes: the cached prefix's K and V read once, the
    chunk's Q, K and V read, its output written."""
    flops = bytes_ = 0.0
    for start, end in spans:
        n = end - start
        if n <= 0:
            continue
        keys = n * start + n * (n + 1) / 2.0  # sum over p of (p + 1)
        flops += 4.0 * heads * head_dim * keys
        bytes_ += BF16 * (2 * kv_heads * head_dim * start
                          + (2 * heads + 2 * kv_heads) * head_dim * n)
    return flops, bytes_


def layer_matmul_params(d_model: int, heads: int, kv_heads: int,
                        head_dim: int, d_ff: int) -> int:
    """Weights one token multiplies through in one decoder layer: Q, K, V
    and O projections and the gated MLP's three matrices."""
    attn = d_model * (heads + 2 * kv_heads) * head_dim \
        + heads * head_dim * d_model
    return attn + 3 * d_model * d_ff


def model_flops(*, layers: int, d_model: int, heads: int, kv_heads: int,
                head_dim: int, d_ff: int, vocab: int,
                prefill_spans: Iterable[Tuple[int, int]],
                decode_contexts: Iterable[int], produced: int) -> float:
    """FLOPs of the served work: every token computed (prompt tokens
    prefilled, tokens decoded) costs 2 * N matmul FLOPs per layer plus
    attention over its live context; every token produced (the first,
    from the prompt's last position, and each decoded one) costs the
    output head, 2 * d_model * vocab."""
    spans = list(prefill_spans)
    contexts = list(decode_contexts)
    n = layer_matmul_params(d_model, heads, kv_heads, head_dim, d_ff)
    tokens = sum(max(e - s, 0) for s, e in spans) + len(contexts)
    attn = prefill_attention(heads, kv_heads, head_dim, spans)[0] \
        + decode_attention(heads, kv_heads, head_dim, contexts)[0]
    return layers * (2.0 * n * tokens + attn) \
        + 2.0 * d_model * vocab * produced


def least_time(flops: float, bytes_: float,
               peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
