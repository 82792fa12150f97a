"""Dense decoder with grouped-query attention (the llama family): every
layer attends its whole live context and runs one gated MLP, so the
layers form one group."""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Tuple

from bench.harness import work
from bench.harness.spec import SpecError

Group = Tuple[int, float, float]   # (layers, flops, bytes)


def model_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file (keys named
    as in the model's published ``config.json``)."""
    from repro.configs.base import ModelConfig
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim", config["hidden_size"] // heads)
    if head_dim * heads != config["hidden_size"] and "head_dim" not in config:
        raise SpecError("hidden_size is not a multiple of the head count")
    return ModelConfig(
        name=config["name"], arch_type="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], num_heads=heads,
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        head_dim=config.get("head_dim"),
        qkv_bias=config.get("attention_bias", False),
        rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        source=config["source"])


def model_flops(dims: Dict[str, Any], prefill_spans: Iterable[Tuple[int, int]],
                decode_contexts: Iterable[int], produced: int) -> float:
    d = dims
    return work.model_flops(
        layers=d["layers"], d_model=d["d"], heads=d["heads"],
        kv_heads=d["kv_heads"], head_dim=d["head_dim"], d_ff=d["ff"],
        vocab=d["vocab"], prefill_spans=prefill_spans,
        decode_contexts=decode_contexts, produced=produced)


def decode_attention(dims: Dict[str, Any],
                     contexts: Iterable[int]) -> List[Group]:
    f, b = work.decode_attention(dims["heads"], dims["kv_heads"],
                                 dims["head_dim"], contexts)
    return [(dims["layers"], f, b)]


def prefill_attention(dims: Dict[str, Any],
                      spans: Iterable[Tuple[int, int]]) -> List[Group]:
    f, b = work.prefill_attention(dims["heads"], dims["kv_heads"],
                                  dims["head_dim"], spans)
    return [(dims["layers"], f, b)]
