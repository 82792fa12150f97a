"""The architecture layer: each configuration's module under ``bench/arch/``
gives the program's ``ModelConfig`` and the work counts the readers of
``mfu``, ``mfu.prefill`` and the two kernel rooflines use.

Every configuration, whatever its family, keeps one contract: its layer
and vocabulary counts as its file states them, and work counts that are
finite, never negative, nought for no work and grouped over no more
layers than it has.  The dense family's own checks run over the
configurations whose file names ``dense_gqa``: there the readers, through
``ctx.arch``, read exactly what the dense formulas they had before gave
with the reference's ``dims``.  A toy MoE architecture, put in place here
as modules in ``sys.modules``, keeps the contract and resolves through
``spec.model_config``, the program's ``build_model`` and the readers,
with no file of the harness changed.
"""
import importlib.util
import json
import math
import numbers
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import check as check_lib
from bench.harness import main as harness
from bench.harness import spec, work
from bench.harness.serving import RoundWork, make_weights
from bench.harness.trace import Event, Trace

BENCH = spec.load_benchmark()
DATA = {c["name"]: json.loads((spec.ROOT / c["file"]).read_text())
        for c in BENCH["configs"]}
CONFIGS = list(DATA)


def dense_only(configs):
    """The configuration files of the dense GQA family."""
    return [c for c in configs if c["reference"] == "dense_gqa"]


DENSE = [c["name"] for c in dense_only(DATA.values())]
READERS = ["mfu", "mfu.prefill", "paged_decode_roofline",
           "paged_prefill_roofline"]
PEAK = work.peaks("TPU v5 lite")

# prefill rounds, decode rounds, and rounds that do both; a span that is
# empty and a round with neither
ROUNDS = [
    RoundWork([(0, 128), (256, 384)], [], 2),
    RoundWork([(1920, 2000)], [2001, 17, 640], 4),
    RoundWork([], [45, 46, 300, 1, 2047], 5),
    RoundWork([(128, 128)], [12], 1),
    RoundWork([], [], 0),
    RoundWork([(0, 3)], [], 1),
]
TRACE = Trace(window_s=10.0, device_ops={"/device:TPU:0": [
    Event("paged_decode_attention.11", 0.5, 0.5625),
    Event("paged_decode_attention.11", 1.0, 1.03125),
    Event("paged_prefill_attention.12", 2.0, 2.75),
    Event("fusion.130", 3.0, 4.0)]}, host_spans=[])
BOUNDS = {"trace_start": 100.0, "trace_stop": 110.25}


def _reader(name):
    spec_ = importlib.util.spec_from_file_location(
        f"test_reader_{name}", spec.metric_path(name))
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod.read


def _ctx(dims, arch):
    return harness.Context(
        cell=None, records=[], bounds=BOUNDS, engine_stats=[],
        max_slots=16, tick_seconds=[], round_work=ROUNDS, dims=dims,
        arch=arch, peak=PEAK, memory={}, trace=TRACE)


# the readers' formulas before the architecture layer, with the dense
# reference's dims
def _dense_flops(d, spans, contexts, produced):
    return work.model_flops(
        layers=d["layers"], d_model=d["d"], heads=d["heads"],
        kv_heads=d["kv_heads"], head_dim=d["head_dim"], d_ff=d["ff"],
        vocab=d["vocab"], prefill_spans=spans, decode_contexts=contexts,
        produced=produced)


def _span():
    return BOUNDS["trace_stop"] - BOUNDS["trace_start"]


def _mfu_before(d):
    total = sum(_dense_flops(d, w.prefill_spans, w.decode_contexts,
                             w.produced) for w in ROUNDS)
    return 100.0 * total / (_span() * PEAK["bf16_flops_per_s"])


def _mfu_prefill_before(d):
    total = 0.0
    for w in ROUNDS:
        if not w.prefill_spans:
            continue
        firsts = w.produced - len(w.decode_contexts)
        total += _dense_flops(d, w.prefill_spans, [], firsts)
    return 100.0 * total / (_span() * PEAK["bf16_flops_per_s"])


def _roofline_before(d, kernel, count, field):
    least = 0.0
    for w in ROUNDS:
        if getattr(w, field):
            f, b = count(d["heads"], d["kv_heads"], d["head_dim"],
                         getattr(w, field))
            least += d["layers"] * work.least_time(f, b, PEAK)
    events = TRACE.device_ops["/device:TPU:0"]
    return 100.0 * least / sum(e.end - e.start for e in events
                               if e.name.startswith(kernel + "."))


BEFORE = {
    "mfu": _mfu_before,
    "mfu.prefill": _mfu_prefill_before,
    "paged_decode_roofline": lambda d: _roofline_before(
        d, "paged_decode_attention", work.decode_attention,
        "decode_contexts"),
    "paged_prefill_roofline": lambda d: _roofline_before(
        d, "paged_prefill_attention", work.prefill_attention,
        "prefill_spans"),
}


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("config", DENSE)
def test_readers_through_the_arch_equal_the_dense_formulas(config, reader):
    data = DATA[config]
    dims = check_lib.reference_module(data).dims(data)
    got = _reader(reader)(_ctx(dims, spec.arch_module(data)))
    want = BEFORE[reader](dims)
    assert got is not None and got > 0
    assert got == want


@pytest.mark.parametrize("config", DENSE)
def test_dense_gqa_reads_the_published_keys(config):
    data = DATA[config]
    mc = spec.model_config(data)
    assert mc.arch_type == "dense" and mc.moe is None
    assert (mc.num_layers, mc.d_model, mc.num_heads, mc.num_kv_heads,
            mc.d_ff, mc.vocab_size, mc.tie_embeddings) == (
        data["num_hidden_layers"], data["hidden_size"],
        data["num_attention_heads"], data["num_key_value_heads"],
        data["intermediate_size"], data["vocab_size"],
        data["tie_word_embeddings"])
    assert mc.rope_theta == data["rope_theta"]
    assert mc.rms_norm_eps == data["rms_norm_eps"]


# ---------------------------------------------------------------------------
# a toy MoE architecture, found by name with no edit to the harness
# ---------------------------------------------------------------------------

TOY = {"name": "toy-moe", "source": "test", "reference": "toy_moe",
       "num_hidden_layers": 4, "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_experts": 4, "num_experts_per_tok": 2,
       "moe_intermediate_size": 16, "vocab_size": 64, "sliding_window": 8,
       "window_layers": 3}


def _toy_dims(config):
    h = config["num_attention_heads"]
    return dict(layers=config["num_hidden_layers"], d=config["hidden_size"],
                heads=h, kv_heads=config["num_key_value_heads"],
                head_dim=config["hidden_size"] // h,
                experts=config["num_experts"],
                k=config["num_experts_per_tok"],
                expert_ff=config["moe_intermediate_size"],
                vocab=config["vocab_size"], window=config["sliding_window"],
                window_layers=config["window_layers"])


def _toy_model_config(config):
    from repro.configs.base import ModelConfig, MoEConfig
    d = _toy_dims(config)
    return ModelConfig(
        name=config["name"], arch_type="moe", num_layers=d["layers"],
        d_model=d["d"], num_heads=d["heads"], num_kv_heads=d["kv_heads"],
        d_ff=d["expert_ff"], vocab_size=d["vocab"],
        moe=MoEConfig(num_experts=d["experts"], experts_per_token=d["k"],
                      d_ff_expert=d["expert_ff"]),
        source=config["source"])


def _toy_decode(d, contexts):
    """Window layers attend at most ``window`` keys, the others all."""
    contexts = list(contexts)
    win = work.decode_attention(d["heads"], d["kv_heads"], d["head_dim"],
                                [min(c, d["window"]) for c in contexts])
    full = work.decode_attention(d["heads"], d["kv_heads"], d["head_dim"],
                                 contexts)
    return [(d["window_layers"], *win),
            (d["layers"] - d["window_layers"], *full)]


def _toy_prefill(d, spans):
    """Window layers: a chunk reads at most ``window`` cached keys before
    it (the attention arithmetic is not the point here)."""
    spans = list(spans)
    win = work.prefill_attention(
        d["heads"], d["kv_heads"], d["head_dim"],
        [(min(s, d["window"]), min(s, d["window"]) + e - s)
         for s, e in spans])
    full = work.prefill_attention(d["heads"], d["kv_heads"], d["head_dim"],
                                  spans)
    return [(d["window_layers"], *win),
            (d["layers"] - d["window_layers"], *full)]


def _toy_model_flops(d, spans, contexts, produced):
    """k experts of ``expert_ff`` and the router per token and layer."""
    spans, contexts = list(spans), list(contexts)
    tokens = sum(max(e - s, 0) for s, e in spans) + len(contexts)
    n = work.layer_matmul_params(d["d"], d["heads"], d["kv_heads"],
                                 d["head_dim"], 0) \
        + d["k"] * 3 * d["d"] * d["expert_ff"] + d["d"] * d["experts"]
    attn = sum(layers * f for layers, f, _ in
               _toy_prefill(d, spans) + _toy_decode(d, contexts))
    return d["layers"] * 2.0 * n * tokens + attn \
        + 2.0 * d["d"] * d["vocab"] * produced


@pytest.fixture
def toy_moe(monkeypatch):
    arch = types.ModuleType("bench.arch.toy_moe")
    arch.model_config = _toy_model_config
    arch.model_flops = _toy_model_flops
    arch.decode_attention = _toy_decode
    arch.prefill_attention = _toy_prefill
    ref = types.ModuleType("bench.reference.toy_moe")
    ref.dims = _toy_dims
    monkeypatch.setitem(sys.modules, arch.__name__, arch)
    monkeypatch.setitem(sys.modules, ref.__name__, ref)
    return arch


def test_a_moe_configuration_resolves_by_its_name(toy_moe):
    assert spec.arch_module(TOY) is toy_moe
    assert check_lib.reference_module(TOY).dims(TOY)["k"] == 2
    mc = spec.model_config(TOY)
    assert mc.arch_type == "moe"
    assert (mc.moe.num_experts, mc.moe.experts_per_token,
            mc.moe.d_ff_expert) == (4, 2, 16)


def test_build_model_gives_the_moe_its_paged_path(toy_moe):
    from repro.models import build_model
    model = build_model(spec.model_config(TOY))
    assert model.init_paged_cache and model.decode_step_paged \
        and model.prefill_chunk_paged
    params = make_weights(model, jax.random.key(5))
    cache = model.init_paged_cache(9, 4, jnp.bfloat16)
    table = jnp.arange(8, dtype=jnp.int32)[None]
    logits, cache = model.prefill_chunk_paged(
        params, cache, jnp.arange(5, dtype=jnp.int32)[None],
        jnp.zeros(1, jnp.int32), jnp.full(1, 5, jnp.int32), table)
    logits, cache = model.decode_step_paged(
        params, cache, jnp.asarray([3], jnp.int32),
        jnp.full(1, 5, jnp.int32), table)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # every served leaf is checksummed, the experts and the router too
    sums = harness._checksums(params)
    assert {"blocks/moe/router", "blocks/moe/up", "final_norm", "embed",
            "lm_head"} <= set(sums)
    assert len(sums) == len(jax.tree.leaves(params))
    assert len(sums["blocks/moe/router"]) == TOY["num_hidden_layers"]


@pytest.mark.parametrize("reader", READERS)
def test_the_readers_use_the_architectures_counts(toy_moe, reader):
    d = _toy_dims(TOY)
    got = _reader(reader)(_ctx(d, toy_moe))
    span = _span()
    events = TRACE.device_ops["/device:TPU:0"]
    if reader.startswith("mfu"):
        prefill = reader == "mfu.prefill"
        total = sum(_toy_model_flops(
            d, w.prefill_spans, [] if prefill else w.decode_contexts,
            w.produced - len(w.decode_contexts) if prefill else w.produced)
            for w in ROUNDS if w.prefill_spans or not prefill)
        want = 100.0 * total / (span * PEAK["bf16_flops_per_s"])
    else:
        kernel, count, field = {
            "paged_decode_roofline": ("paged_decode_attention",
                                      _toy_decode, "decode_contexts"),
            "paged_prefill_roofline": ("paged_prefill_attention",
                                       _toy_prefill, "prefill_spans"),
        }[reader]
        least = sum(layers * work.least_time(f, b, PEAK)
                    for w in ROUNDS if getattr(w, field)
                    for layers, f, b in count(d, getattr(w, field)))
        want = 100.0 * least / sum(e.end - e.start for e in events
                                   if e.name.startswith(kernel + "."))
    assert got == pytest.approx(want, rel=1e-12)
    # and not what a dense model of the same widths would count
    dense = dict(d, ff=d["k"] * d["expert_ff"])
    assert got != pytest.approx(BEFORE[reader](dense), rel=1e-6)


# ---------------------------------------------------------------------------
# the contract every family keeps
# ---------------------------------------------------------------------------

def _has_work(w):
    return any(e > s for s, e in w.prefill_spans) or bool(
        w.decode_contexts) or w.produced > 0


def _check_groups(groups, layers):
    assert groups is not None
    for n, f, b in groups:
        assert isinstance(n, numbers.Integral) and not isinstance(
            n, bool) and n > 0
        assert math.isfinite(f) and f >= 0
        assert math.isfinite(b) and b >= 0
    assert sum(n for n, _, _ in groups) <= layers


@pytest.mark.parametrize("config", CONFIGS + [TOY["name"]])
def test_every_family_keeps_the_contract(config, request):
    if config == TOY["name"]:
        request.getfixturevalue("toy_moe")
        data = TOY
    else:
        data = DATA[config]
    layers = data["num_hidden_layers"]
    arch = spec.arch_module(data)
    dims = check_lib.reference_module(data).dims(data)
    mc = spec.model_config(data)
    assert (mc.num_layers, mc.vocab_size) == (layers, data["vocab_size"])
    assert dims["layers"] == layers
    assert any(not _has_work(w) for w in ROUNDS)
    for w in ROUNDS:
        flops = arch.model_flops(dims, w.prefill_spans, w.decode_contexts,
                                 w.produced)
        assert math.isfinite(flops)
        assert flops > 0 if _has_work(w) else flops == 0
        _check_groups(arch.decode_attention(dims, w.decode_contexts), layers)
        _check_groups(arch.prefill_attention(dims, w.prefill_spans), layers)
    for groups in (arch.decode_attention(dims, []),
                   arch.prefill_attention(dims, [])):
        _check_groups(groups, layers)
        assert all(f == 0 and b == 0 for _, f, b in groups)


def test_the_dense_checks_leave_other_families_out():
    chosen = dense_only([*DATA.values(), TOY])
    assert TOY not in chosen
    assert [c["name"] for c in chosen] == DENSE
