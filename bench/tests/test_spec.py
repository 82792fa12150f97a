"""BENCHMARK.json against the files the harness finds by name, and the
limits its format sets."""
import json
import re

import pytest

from bench.harness import check as check_lib
from bench.harness import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_names_units_and_sources():
    names = [m["name"] for m in METRICS] + CELLS \
        + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in [w["why"] for w in BENCH["workloads"] + BENCH["configs"]] \
            + [m["layer"] for m in BENCH["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_and_reports_enough(cell):
    c = spec.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, m
        assert spec.metric_path(m["name"]).is_file(), m["name"]
    assert spec.model_config(c.config).num_layers \
        == c.config["num_hidden_layers"]
    limits = json.loads((spec.BENCH_DIR / "limits" / f"{cell}.json")
                        .read_text())
    assert limits["max_logit_gap"]["limit"] > 0


# widths, which a configuration never cuts (the model-configs guide,
# section 4): hidden, intermediate and expert, latent, state, projection
# and head sizes, ranks, the window, the expansion factor and the experts
# each token takes
WIDTH_SUFFIXES = ("_dim", "_rank", "hidden_size", "intermediate_size",
                  "state_size", "head_size", "proj_size")
WIDTH_KEYS = ("sliding_window", "expand", "num_experts_per_tok")
VOCAB_SHARE = 8   # a configuration keeps at least 1/8 of the vocabulary


def is_width(key):
    return key.endswith(WIDTH_SUFFIXES) or key in WIDTH_KEYS


@pytest.mark.parametrize("key, width", [
    ("head_dim", True), ("kv_lora_rank", True), ("hidden_size", True),
    ("intermediate_size", True), ("moe_intermediate_size", True),
    ("shared_expert_intermediate_size", True), ("sliding_window", True),
    ("state_size", True), ("num_experts_per_tok", True), ("expand", True),
    ("vocab_size", False), ("num_hidden_layers", False),
    ("num_experts", False), ("logits_scaling", False)])
def test_the_width_rule(key, width):
    assert is_width(key) is width


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cuts(config):
    data = json.loads((spec.ROOT / config["file"]).read_text())
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert key in data and key in data["published"]
        assert not is_width(key), key
    if "vocab_size" in config["reduced"]:
        assert data["vocab_size"] * VOCAB_SHARE \
            >= data["published"]["vocab_size"]


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_every_reference_names_a_reference_and_an_architecture(config):
    data = json.loads((spec.ROOT / config["file"]).read_text())
    name = data["reference"]
    assert (spec.BENCH_DIR / "reference" / f"{name}.py").is_file()
    assert (spec.BENCH_DIR / "arch" / f"{name}.py").is_file()
    arch = spec.arch_module(data)
    for fn in ("model_config", "model_flops", "decode_attention",
               "prefill_attention"):
        assert callable(getattr(arch, fn)), fn
    assert callable(check_lib.reference_module(data).dims)


def test_the_benchmark_lives_under_its_paths():
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
