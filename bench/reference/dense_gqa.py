"""Plain float32 reference of a dense decoder with grouped-query attention
(the llama family: RMSNorm, rotary positions on the rotate-half pairs,
causal softmax attention with KV heads shared by query groups, a SwiGLU
MLP, an output head tied to the embedding or not).

It imports nothing of the program and takes nothing the program made.
The weights are drawn again from the run's seed by the same random calls,
in the same order and shapes, as the served weights are drawn: float32
draws rounded to bf16, which the forward pass then computes with in
float32 at ``Precision.HIGHEST``.  It runs layer by layer over every
sequence it is given (one layer's weights on the device at a time,
attention and MLP in blocks of rows), so it fits one chip beside nothing
else.

Departure from granite-3.0's published equations, shared with the
program: no embedding, attention, residual or logits multipliers (the
configuration file states them as run).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROWS = 256  # rows per block of attention queries and of the MLP


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    h = config["num_attention_heads"]
    d = config["hidden_size"]
    return dict(
        layers=config["num_hidden_layers"], d=d, heads=h,
        kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim", d // h),
        ff=config["intermediate_size"], vocab=config["vocab_size"],
        padded_vocab=-(-config["vocab_size"] // 256) * 256,
        tied=bool(config["tie_word_embeddings"]),
        bias=bool(config.get("attention_bias", False)),
        theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]))


def _frozen(config):
    return tuple(sorted(dims(config).items()))


# ---------------------------------------------------------------------------
# weights from the seed, one part at a time
# ---------------------------------------------------------------------------

def _dense(key, n_in: int, n_out: int, dtype=jnp.float32):
    return (1.0 / math.sqrt(n_in)) * jax.random.truncated_normal(
        key, -2.0, 2.0, (n_in, n_out), dtype)


def _served(tree):
    """Drawn in float32, served in bf16 (``bench/harness/serving.py``)."""
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)


def _block(key, m, dtype=jnp.float32):
    ka, km = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    hd = m["head_dim"]
    attn = {"wq": _dense(kq, m["d"], m["heads"] * hd, dtype),
            "wk": _dense(kk, m["d"], m["kv_heads"] * hd, dtype),
            "wv": _dense(kv, m["d"], m["kv_heads"] * hd, dtype),
            "wo": _dense(ko, m["heads"] * hd, m["d"], dtype)}
    if m["bias"]:
        attn["bq"] = jnp.zeros((m["heads"] * hd,), dtype)
        attn["bk"] = jnp.zeros((m["kv_heads"] * hd,), dtype)
        attn["bv"] = jnp.zeros((m["kv_heads"] * hd,), dtype)
    p = {"attn_norm": jnp.ones((m["d"],), dtype), "attn": attn,
         "mlp_norm": jnp.ones((m["d"],), dtype)}
    k1, k2, k3 = jax.random.split(km, 3)
    p["mlp"] = {"gate": _dense(k1, m["d"], m["ff"], dtype),
                "up": _dense(k2, m["d"], m["ff"], dtype),
                "down": _dense(k3, m["ff"], m["d"], dtype)}
    return p


def _keys(key, layers: int):
    ke, kb, kh = jax.random.split(key, 3)
    return ke, jax.random.split(kb, layers), kh


@functools.partial(jax.jit, static_argnums=(2,))
def _layer_weights(key, layer, frozen):
    m = dict(frozen)
    return _served(_block(_keys(key, m["layers"])[1][layer], m))


@functools.partial(jax.jit, static_argnums=(1,))
def _embed(key, frozen):
    m = dict(frozen)
    ke = _keys(key, m["layers"])[0]
    return _served(jax.random.normal(ke, (m["padded_vocab"], m["d"]),
                                     jnp.float32) * 0.02)


@functools.partial(jax.jit, static_argnums=(1,))
def _lm_head(key, frozen):
    m = dict(frozen)
    return _served(_dense(_keys(key, m["layers"])[2], m["d"],
                          m["padded_vocab"]))


def layer_weights(config, key, layer: int):
    """Decoder layer ``layer``'s served weights (bf16)."""
    return _layer_weights(key, layer, _frozen(config))


def embed_weights(config, key):
    return _embed(key, _frozen(config))


def head_weights(config, key, embed=None):
    """The output head as (d_model, padded vocab) bf16."""
    if dims(config)["tied"]:
        return (embed_weights(config, key) if embed is None else embed).T
    return _lm_head(key, _frozen(config))


# ---------------------------------------------------------------------------
# forward pass in float32
# ---------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, positions, theta):
    """x: (T, heads, hd); rotate the (first half, second half) pairs."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv      # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def round_e4m3(x):
    """float32 values rounded to the nearest float8 e4m3 value (ties to
    even), kept in float32.  Done with integer and rounding operations,
    not a pair of casts: XLA on the TPU drops a float32 -> float8 ->
    float32 round trip as excess precision, which would leave the control
    in float32.  Normal values keep 3 mantissa bits; below 2**-6 the
    spacing is 2**-9; the range ends at +-448."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFFF) + ((bits >> 20) & 1)) \
        & jnp.uint32(0xFFF00000)
    normal = jax.lax.bitcast_convert_type(bits, jnp.float32)
    sub = jnp.round(x * 512.0) / 512.0
    return jnp.clip(jnp.where(jnp.abs(x) < 2.0 ** -6, sub, normal),
                    -448.0, 448.0)


def _weight(w, fp8: bool):
    """A weight as the forward pass uses it: its bf16 value in float32, or
    for the control rounded to float8 e4m3 first."""
    w = w.astype(jnp.float32)
    return round_e4m3(w) if fp8 else w


def _mm(x, w, fp8: bool):
    """x @ w, where w has passed through ``_weight``; for the fp8 control
    the input is rounded to e4m3 too (an fp8 matmul rounds both
    operands)."""
    if fp8:
        x = round_e4m3(x)
    return jnp.matmul(x, w, precision=HI)


def _one_sequence(x, w, m, fp8):
    """One decoder layer over one sequence x: (T, d)."""
    T = x.shape[0]
    hd, H, KVH = m["head_dim"], m["heads"], m["kv_heads"]
    g = H // KVH
    a = w["attn"]
    positions = jnp.arange(T)
    h = _rms_norm(x, w["attn_norm"], m["eps"])
    q, k, v = (_mm(h, a["wq"], fp8), _mm(h, a["wk"], fp8),
               _mm(h, a["wv"], fp8))
    if m["bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(T, H, hd), positions, m["theta"])
    k = _rope(k.reshape(T, KVH, hd), positions, m["theta"])
    v = v.reshape(T, KVH, hd)

    def attend(blk):
        qb, pos = blk                                   # (R, H, hd), (R,)
        s = jnp.einsum("rkgd,skd->kgrs", qb.reshape(-1, KVH, g, hd), k,
                       precision=HI) / math.sqrt(hd)
        s = jnp.where(pos[:, None] >= positions[None, :], s, -jnp.inf)
        o = jnp.einsum("kgrs,skd->rkgd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI)
        return o.reshape(-1, H * hd)

    o = jax.lax.map(attend, (q.reshape(T // ROWS, ROWS, H, hd),
                             positions.reshape(T // ROWS, ROWS)))
    x = x + _mm(o.reshape(T, H * hd), a["wo"], fp8)
    mp = w["mlp"]

    def mlp(xb):
        hb = _rms_norm(xb, w["mlp_norm"], m["eps"])
        up = jax.nn.silu(_mm(hb, mp["gate"], fp8)) * _mm(hb, mp["up"], fp8)
        return xb + _mm(up, mp["down"], fp8)

    return jax.lax.map(mlp, x.reshape(T // ROWS, ROWS, -1)).reshape(T, -1)


@functools.partial(jax.jit, static_argnums=(2, 3), donate_argnums=(0,))
def _layer(hidden, lw, frozen, fp8):
    """One decoder layer over hidden (n, T, d) float32, one sequence at a
    time."""
    m = dict(frozen)
    w = jax.tree.map(lambda a: _weight(a, fp8) if a.ndim == 2
                     else a.astype(jnp.float32), lw)
    return jax.lax.map(lambda x: _one_sequence(x, w, m, fp8), hidden)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(hidden, head, frozen, fp8):
    """Final norm (its scale is drawn as ones) and logits over the
    vocabulary, a block of the head's columns at a time: hidden (n, d) ->
    (n, vocab)."""
    m = dict(frozen)
    h = _rms_norm(hidden, 1.0, m["eps"])
    n_blocks = 8
    cols = head.shape[1] // n_blocks
    blocks = head.reshape(head.shape[0], n_blocks, cols).transpose(1, 0, 2)
    out = jax.lax.map(lambda wb: _mm(h, _weight(wb, fp8), fp8), blocks)
    return out.transpose(1, 0, 2).reshape(h.shape[0], -1)[:, :m["vocab"]]


def bucket(n: int) -> int:
    """Sequences are padded to a power of two (at least ``ROWS``), so a run
    compiles a few programs; causal attention keeps the pad from touching
    the positions read."""
    b = ROWS
    while b < n:
        b *= 2
    return b


def logits(config: Dict[str, Any], key, sequences: Sequence[Sequence[int]],
           reads: Sequence[Sequence[int]], control: bool = False) -> Dict:
    """float32 logits at positions ``reads[i]`` of ``sequences[i]``: the
    reference's under "reference" and, with ``control``, the fp8
    control's under "control", each a list of arrays (len(reads[i]),
    vocab), one per sequence."""
    frozen = _frozen(config)
    m = dict(frozen)
    runs = {"reference": False, "control": True} if control \
        else {"reference": False}
    embed = embed_weights(config, key)
    hidden = {}
    for i, s in enumerate(sequences):
        tok = np.zeros((1, bucket(len(s))), np.int32)
        tok[0, :len(s)] = s
        for name in runs:
            # one buffer per sequence and run, so that a run compiles one
            # program per length bucket whatever its sample; each layer
            # call donates its input
            hidden[(i, name)] = embed[jnp.asarray(tok)].astype(jnp.float32)
    if not m["tied"]:
        del embed
        embed = None
    for layer in range(m["layers"]):
        lw = layer_weights(config, key, layer)
        for k, x in list(hidden.items()):
            hidden[k] = _layer(x, lw, frozen, runs[k[1]])
        del lw
    head = head_weights(config, key, embed)
    out = {}
    for name, fp8 in runs.items():
        rows = jnp.concatenate([hidden.pop((i, name))[0][jnp.asarray(r)]
                                for i, r in enumerate(reads)])
        n = rows.shape[0]
        rows = jnp.pad(rows, ((0, bucket(n) - n), (0, 0)))
        got = np.asarray(_head(rows, head, frozen, fp8))
        ends = np.cumsum([len(r) for r in reads])
        out[name] = np.split(got[:n], ends[:-1])
    return out


def checksums(config: Dict[str, Any], key) -> Dict[str, List[int]]:
    """Bitwise checksums of the weights drawn from ``key``, one part at a
    time: for each leaf, the sum of its bf16 bit patterns (per layer for
    the decoder layers)."""
    m = dims(config)
    out: Dict[str, List[int]] = {}
    for layer in range(m["layers"]):
        for path, a in _flat(layer_weights(config, key, layer)):
            out.setdefault(f"blocks/{path}", []).append(_bits(a))
    out["embed"] = [_bits(embed_weights(config, key))]
    # the final norm's scale is drawn as ones, as ``_head`` applies it
    out["final_norm"] = [_bits(jnp.ones((m["d"],), jnp.bfloat16))]
    if not m["tied"]:
        out["lm_head"] = [_bits(head_weights(config, key))]
    return out


def _flat(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _flat(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}", v


@jax.jit
def _bit_sum(a):
    return jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint16)
                   .astype(jnp.uint32))


def _bits(a) -> int:
    return int(_bit_sum(a))
