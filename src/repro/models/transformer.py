"""Decoder-only transformer LM (dense / MoE / VLM backbone).

Per-layer params are stacked on a leading ``layers`` axis and the forward
pass is a ``jax.lax.scan`` over blocks (keeps HLO size O(1) in depth — 95
layers for deepseek-67b — and gives the remat boundary for training).

Serving-cache donation contract: the engine jits ``decode_step(_paged)``
and ``prefill_chunk(_paged)`` with the cache pytree DONATED
(``jax.jit(..., donate_argnums)``), so every cache leaf here must be
update-in-place friendly — the functional ``.at[].set`` writes are the
only consumers of the incoming buffers, and any attention read of "the
cache as it was on entry" must be expressible against the post-write
arrays (see the donation notes in ``models/attention.py``; rolling SWA is
the one path that genuinely needs the pre-write copy).  The per-layer
``lax.scan`` keeps this property: the stacked cache rides as scan
xs/ys, which XLA aliases when the donated input allows it.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, layers, moe as moe_lib


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(key, cfg, dtype=jnp.float32):
    ka, km = jax.random.split(key)
    p = {
        "attn_norm": jnp.ones((cfg.d_model,), dtype),
        "attn": attention.init_attention(ka, cfg, dtype),
        "mlp_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.moe is not None:
        p["moe"] = moe_lib.init_moe(km, cfg, dtype)
    else:
        p["mlp"] = layers.init_swiglu_mlp(km, cfg.d_model, cfg.d_ff, dtype)
    return p


def block_param_axes(cfg):
    p = {
        "attn_norm": ("embed",),
        "attn": attention.attention_param_axes(cfg),
        "mlp_norm": ("embed",),
    }
    if cfg.moe is not None:
        p["moe"] = moe_lib.moe_param_axes(cfg)
    else:
        p["mlp"] = {"gate": ("embed", "ff"), "up": ("embed", "ff"),
                    "down": ("ff", "embed")}
    return p


def init_lm(key, cfg, dtype=jnp.float32):
    ke, kb, kh = jax.random.split(key, 3)
    block_keys = jax.random.split(kb, cfg.num_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg, dtype))(block_keys)
    p = {
        "embed": layers.embed_init(ke, cfg.padded_vocab, cfg.d_model, dtype),
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.dense_init(kh, cfg.d_model, cfg.padded_vocab, dtype)
    if cfg.vision is not None:
        kp = jax.random.fold_in(kh, 1)
        in_dim = cfg.vision.patch_embed_dim or cfg.d_model
        p["vision_proj"] = layers.dense_init(kp, in_dim, cfg.d_model, dtype)
    return p


def lm_param_axes(cfg):
    ax = {
        "embed": ("vocab", "embed"),
        "blocks": jax.tree.map(lambda a: a, block_param_axes(cfg)),
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        ax["lm_head"] = ("embed", "vocab")
    if cfg.vision is not None:
        ax["vision_proj"] = ("embed", "embed_in")
    return ax


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block_train(cfg, x, positions, bp):
    h = layers.rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps)
    x = x + attention.attend_train(bp["attn"], cfg, h, positions)
    h = layers.rms_norm(x, bp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.moe is not None:
        out, aux = moe_lib.apply_moe(bp["moe"], cfg, h)
    else:
        out, aux = layers.swiglu_mlp(bp["mlp"], h), jnp.float32(0.0)
    return x + out, aux


def _seq_shard(cfg, x):
    """Perf lever (EXPERIMENTS §Perf H1): keep residual activations sharded
    on the seq dim over the 'model' axis between blocks — cuts the saved
    remat residuals by the TP degree."""
    if not cfg.shard_activations_seq:
        return x
    from jax.sharding import PartitionSpec as P
    U = P.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(x, P(U, "model", U))


def forward_train(params, cfg, x_embeds: jax.Array, positions: jax.Array,
                  *, remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """x_embeds: (B, L, d) -> (hidden (B, L, d), total_aux_loss)."""
    block = functools.partial(_block_train, cfg)
    if remat:
        block = jax.checkpoint(block, static_argnums=())

    def scan_fn(carry, bp):
        x, aux = carry
        x, a = block(x, positions, bp)
        return (_seq_shard(cfg, x), aux + a), None

    (x, aux), _ = jax.lax.scan(scan_fn, (_seq_shard(cfg, x_embeds), jnp.float32(0.0)),
                               params["blocks"])
    return layers.rms_norm(x, params["final_norm"], cfg.rms_norm_eps), aux


def embed_tokens(params, cfg, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens]


def unembed(params, cfg, x: jax.Array) -> jax.Array:
    logits = x @ (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return layers.mask_padded_logits(logits, cfg.vocab_size)


def embed_vlm(params, cfg, tokens: jax.Array, patch_embeds: jax.Array) -> jax.Array:
    """VLM input: precomputed patch embeddings (stub frontend) projected and
    prepended to the token embeddings."""
    tok = embed_tokens(params, cfg, tokens)
    patches = patch_embeds @ params["vision_proj"]
    return jnp.concatenate([patches.astype(tok.dtype), tok], axis=1)


def loss_fn(params, cfg, batch: Dict[str, jax.Array],
            *, remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE. batch: {"tokens": (B, S+1) int32[, "patch_embeds"]}"""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if cfg.vision is not None:
        x = embed_vlm(params, cfg, inputs, batch["patch_embeds"])
        n_prefix = x.shape[1] - inputs.shape[1]
    else:
        x = embed_tokens(params, cfg, inputs)
        n_prefix = 0
    B, L, _ = x.shape
    positions = jnp.arange(L)[None, :]
    hidden, aux = forward_train(params, cfg, x, positions, remat=remat)
    hidden = hidden[:, n_prefix:]
    logits = unembed(params, cfg, hidden).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    total = ce + aux_w * aux / max(cfg.num_layers, 1)
    return total, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# serving paths
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.float32):
    """Stacked per-layer KV cache: leaves (layers, B, KVH, S, D)."""
    one = attention.init_kv_cache(cfg, batch, max_seq, dtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape), one)


def _block_prefill(cfg, x, positions, bp, cache_layer):
    h = layers.rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps)
    a, new_cache = attention.attend_prefill(bp["attn"], cfg, h, positions, cache_layer)
    x = x + a
    h = layers.rms_norm(x, bp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.moe is not None:
        out, _ = moe_lib.apply_moe(bp["moe"], cfg, h)
    else:
        out = layers.swiglu_mlp(bp["mlp"], h)
    return x + out, new_cache


def prefill(params, cfg, tokens: jax.Array, cache,
            patch_embeds: Optional[jax.Array] = None):
    """tokens: (B, L). Returns (last-position logits (B, V), new cache)."""
    if cfg.vision is not None:
        assert patch_embeds is not None
        x = embed_vlm(params, cfg, tokens, patch_embeds)
    else:
        x = embed_tokens(params, cfg, tokens)
    L = x.shape[1]
    positions = jnp.arange(L)[None, :]

    def scan_fn(x, inp):
        bp, cl = inp
        x, new_cl = _block_prefill(cfg, x, positions, bp, cl)
        return x, new_cl

    x, new_cache = jax.lax.scan(scan_fn, x, (params["blocks"], cache))
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return unembed(params, cfg, x[:, -1]), new_cache


def _block_prefill_chunk(cfg, x, positions, valid, bp, cache_layer):
    h = layers.rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps)
    a, new_cache = attention.attend_prefill_chunk(bp["attn"], cfg, h,
                                                  positions, valid, cache_layer)
    x = x + a
    h = layers.rms_norm(x, bp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.moe is not None:
        out, _ = moe_lib.apply_moe(bp["moe"], cfg, h)
    else:
        out = layers.swiglu_mlp(bp["mlp"], h)
    return x + out, new_cache


def prefill_chunk(params, cfg, tokens: jax.Array, starts: jax.Array,
                  valid: jax.Array, cache):
    """One chunk of a chunked prefill over a continuous batch.

    tokens: (B, C) right-padded chunk tokens; starts: (B,) tokens already
    cached per sequence; valid: (B,) real tokens in each row (0 = inactive
    row: no cache writes, output ignored).  Returns (logits at each row's
    last valid position (B, V), new cache) — the logits are only meaningful
    for rows whose chunk is the final one of their prompt.
    """
    x = embed_tokens(params, cfg, tokens)
    B, C, _ = x.shape
    positions = starts[:, None] + jnp.arange(C)[None, :]

    def scan_fn(x, inp):
        bp, cl = inp
        x, new_cl = _block_prefill_chunk(cfg, x, positions, valid, bp, cl)
        return x, new_cl

    x, new_cache = jax.lax.scan(scan_fn, x, (params["blocks"], cache))
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    last = jnp.clip(valid - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return unembed(params, cfg, x_last), new_cache


def _scan_layers_in_place(layer_fn, x, blocks, cache):
    """Run ``layer_fn(x, block_params, layer_cache) -> (x, layer_cache)``
    over the layer stack with the stacked page pool in the scan CARRY:
    each layer's pages are read and written back at their own layer
    index, so the pool is updated in place.  (Scanning the pool as xs and
    collecting the new pool as ys holds a second whole pool per step.)"""
    def body(carry, inp):
        x, cache = carry
        bp, layer = inp
        cl = jax.tree.map(lambda a: a[layer], cache)
        x, new_cl = layer_fn(x, bp, cl)
        cache = jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, layer, 0),
            cache, new_cl)
        return (x, cache), None

    n_layers = jax.tree.leaves(cache)[0].shape[0]
    (x, cache), _ = jax.lax.scan(body, (x, cache),
                                 (blocks, jnp.arange(n_layers)))
    return x, cache


def init_paged_cache(cfg, num_blocks: int, block_size: int,
                     dtype=jnp.float32):
    """Stacked per-layer KV page pool: leaves (layers, num_blocks, KVH,
    block_size, D) — no per-slot batch axis; sequences share the pool via
    their block tables."""
    one = attention.init_paged_kv_cache(cfg, num_blocks, block_size, dtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape), one)


def _block_prefill_chunk_paged(cfg, x, positions, valid, block_table, bp,
                               cache_layer):
    h = layers.rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps)
    a, new_cache = attention.attend_prefill_chunk_paged(
        bp["attn"], cfg, h, positions, valid, block_table, cache_layer)
    x = x + a
    h = layers.rms_norm(x, bp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.moe is not None:
        out, _ = moe_lib.apply_moe(bp["moe"], cfg, h)
    else:
        out = layers.swiglu_mlp(bp["mlp"], h)
    return x + out, new_cache


def prefill_chunk_paged(params, cfg, tokens: jax.Array, starts: jax.Array,
                        valid: jax.Array, block_table: jax.Array, cache):
    """``prefill_chunk`` against the paged KV pool: same contract, plus the
    per-sequence ``block_table`` (B, nb) naming the pages each row's chunk
    writes into (one table for all layers — each layer has its own pool).
    With ``cfg.use_pallas_attention`` every layer's chunk attention runs
    the fused paged prefill kernel (pages streamed in place, no gather)."""
    x = embed_tokens(params, cfg, tokens)
    B, C, _ = x.shape
    positions = starts[:, None] + jnp.arange(C)[None, :]
    x, new_cache = _scan_layers_in_place(
        lambda x, bp, cl: _block_prefill_chunk_paged(
            cfg, x, positions, valid, block_table, bp, cl),
        x, params["blocks"], cache)
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    last = jnp.clip(valid - 1, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return unembed(params, cfg, x_last), new_cache


def _block_decode_paged(cfg, x, lengths, block_table, bp, cache_layer):
    h = layers.rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps)
    a, new_cache = attention.attend_decode_paged(bp["attn"], cfg, h, lengths,
                                                 block_table, cache_layer)
    x = x + a
    h = layers.rms_norm(x, bp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.moe is not None:
        out, _ = moe_lib.apply_moe(bp["moe"], cfg, h)
    else:
        out = layers.swiglu_mlp(bp["mlp"], h)
    return x + out, new_cache


def decode_step_paged(params, cfg, tokens: jax.Array, lengths: jax.Array,
                      block_table: jax.Array, cache):
    """``decode_step`` against the paged KV pool (block_table: (B, nb))."""
    x = embed_tokens(params, cfg, tokens[:, None])
    x, new_cache = _scan_layers_in_place(
        lambda x, bp, cl: _block_decode_paged(cfg, x, lengths, block_table,
                                              bp, cl),
        x, params["blocks"], cache)
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return unembed(params, cfg, x[:, 0]), new_cache


def _block_decode(cfg, x, lengths, bp, cache_layer):
    h = layers.rms_norm(x, bp["attn_norm"], cfg.rms_norm_eps)
    a, new_cache = attention.attend_decode(bp["attn"], cfg, h, lengths, cache_layer)
    x = x + a
    h = layers.rms_norm(x, bp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.moe is not None:
        out, _ = moe_lib.apply_moe(bp["moe"], cfg, h)
    else:
        out = layers.swiglu_mlp(bp["mlp"], h)
    return x + out, new_cache


def decode_step(params, cfg, tokens: jax.Array, lengths: jax.Array, cache):
    """tokens: (B,) int32, lengths: (B,) current cache fill per sequence.
    Returns (logits (B, V), new cache)."""
    x = embed_tokens(params, cfg, tokens[:, None])

    def scan_fn(x, inp):
        bp, cl = inp
        x, new_cl = _block_decode(cfg, x, lengths, bp, cl)
        return x, new_cl

    x, new_cache = jax.lax.scan(scan_fn, x, (params["blocks"], cache))
    x = layers.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return unembed(params, cfg, x[:, 0]), new_cache
