"""A whole run on the CPU at a tiny size, past the harness's look for a
chip: sound, it comes out ``correct``; with the served path broken under
it, ``correct`` comes out false, once for each fault a served cell can
have (one chip: no exchange between chips to leave out), and when the
served weights are not the reference's; and the control, the reference in
fp8 put in the program's place, comes out not correct.

The tiny model serves bf16 weights on the CPU, so its gaps are not the
chip's: the limit here is this size's own, set the same way (above what
sound runs read, below what the faults and the control read).
"""
import dataclasses

import jax.numpy as jnp
import pytest

from bench.harness import main as harness
from bench.harness.spec import Cell, load_cell

TINY_LIMIT = {"max_logit_gap": {"limit": 0.02}}


@pytest.fixture(scope="module")
def tiny_cell():
    cell = load_cell("granite-3-2b.chat-mixed")
    config = dict(cell.config, num_hidden_layers=2, hidden_size=64,
                  num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=128, vocab_size=300)
    config["engine"] = dict(config["engine"], slots=4, max_seq_len=96)
    # arrivals close enough that requests overlap and fill several slots
    traffic = dict(cell.traffic, rate_per_s=12.0, check_tokens=48,
                   prompt_tokens=dict(cell.traffic["prompt_tokens"], max=40),
                   output_tokens=dict(cell.traffic["output_tokens"], max=16))
    return Cell(name="tiny", chips=1, config=config, traffic=traffic,
                end_to_end=cell.end_to_end, per_layer=cell.per_layer)


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")


def _run(cell, fault=None, control=False):
    return harness.run(cell, seed=2**31 + 3, seconds=1.0, trace=False,
                       require_tpu=False, limits=TINY_LIMIT, fault=fault,
                       control=control)


def _wrap_tokens(engine, alter):
    """Alter the tokens the engine's decode and burst steps produce."""
    decode, burst = engine._decode_fn, engine._burst_fn

    def decode_fn(*a):
        tok, cache = decode(*a)
        return alter(tok), cache

    def burst_fn(*a):
        out, cache = burst(*a)
        return jnp.where(out >= 0, alter(out), out), cache
    engine._decode_fn, engine._burst_fn = decode_fn, burst_fn


def token_altered(served):
    """A token altered where it is produced: every decoded token of the
    first slot is moved to the next vocabulary id."""
    for e in served.engines:
        _wrap_tokens(e, lambda t: t.at[..., 0].set(
            (t[..., 0] + 1) % served.model_cfg.vocab_size))


def half_batch(served):
    """Half of the batch left out: the odd slots' decode is never
    computed, and they emit token 0."""
    for e in served.engines:
        _wrap_tokens(e, lambda t: t.at[..., 1::2].set(0))


def state_unchanged(served):
    """A step that returns its state unchanged: the prefill chunk's KV
    writes never reach the pool (the pool it returns is the one it got)."""
    for e in served.engines:
        e.cfg.donate_buffers = False
        e._jit_compute()
        chunk = e._chunk_fn

        def chunk_fn(params, cache, *a, _chunk=chunk):
            tok, _ = _chunk(params, cache, *a)
            return tok, cache
        e._chunk_fn = chunk_fn


def weights_changed(served):
    """The served weights are not the reference's: one bf16 step on one
    element of the served embedding."""
    for e in served.engines:
        emb = e.params["embed"]
        e.params = dict(e.params, embed=emb.at[0, 0].set(
            jnp.nextafter(emb[0, 0], jnp.asarray(1.0, emb.dtype))))
    model = served.registry[served.name][0]
    served.registry[served.name] = (model, served.engines[0].params)


def final_norm_changed(served):
    """A served leaf outside the decoder layers, the embedding and the
    head is not the reference's: one bf16 step on the final norm."""
    for e in served.engines:
        norm = e.params["final_norm"]
        e.params = dict(e.params, final_norm=norm.at[0].set(
            jnp.nextafter(norm[0], jnp.asarray(2.0, norm.dtype))))
    model = served.registry[served.name][0]
    served.registry[served.name] = (model, served.engines[0].params)


def test_a_sound_run_is_correct(tiny_cell):
    res = _run(tiny_cell)
    assert res["correct"], res["check"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tokens_per_s", "tpot_p95_ms",
                                   "interactive_slo_attainment", "setup_s"}
    assert res["check"]["weights_differing"]["value"] == 0
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("fault", [token_altered, half_batch,
                                   state_unchanged, weights_changed,
                                   final_norm_changed])
def test_a_broken_path_is_not_correct(tiny_cell, fault):
    res = _run(tiny_cell, fault=fault)
    assert not res["correct"], res["check"]


def test_the_control_reads_wider_than_the_program(tiny_cell):
    # every request finished and every served token compared, so the
    # sample does not hang on how far a loaded CPU got, nor on which slot
    # served which request
    traffic = tiny_cell.traffic
    cell = dataclasses.replace(tiny_cell, traffic=dict(
        traffic, drain_s=10.0,
        check_tokens=12 * traffic["output_tokens"]["max"]))
    res = _run(cell, control=True)
    assert res["correct"] and res["control"]["correct"] is False
    assert res["control"]["max_logit_gap"]["value"] \
        > TINY_LIMIT["max_logit_gap"]["limit"] \
        >= res["check"]["max_logit_gap"]["value"]
