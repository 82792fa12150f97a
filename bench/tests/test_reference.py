"""The plain reference against the program, on the CPU at a reduced size
of each configuration's shape family (granite: tied head, 4 query heads
per KV head; deepseek: untied head, 8 per KV head).

The reference draws the same bf16 weights from the seed as the served
registry does, and its float32 logits match the program's prefill chunk
followed by paged decode steps, run in float32 on those weights.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness.serving import make_weights
from bench.harness.spec import model_config
from bench.reference import dense_gqa

FAMILIES = {
    "granite": dict(num_hidden_layers=2, hidden_size=64,
                    num_attention_heads=8, num_key_value_heads=2,
                    intermediate_size=128, vocab_size=300,
                    tie_word_embeddings=True, rope_theta=10000.0,
                    rms_norm_eps=1e-5),
    "deepseek": dict(num_hidden_layers=2, hidden_size=128,
                     num_attention_heads=8, num_key_value_heads=1,
                     intermediate_size=160, vocab_size=520,
                     tie_word_embeddings=False, rope_theta=10000.0,
                     rms_norm_eps=1e-6),
}
# float32 on both sides, the same weights: only the order of summation
# differs (paged kernels in interpret mode, online softmax)
ATOL = 2e-4


def _config(family):
    return dict(FAMILIES[family], name=f"tiny-{family}", source="test",
                reference="dense_gqa")


def _program(config, key):
    from repro.models import build_model
    cfg = dataclasses.replace(model_config(config),
                              use_pallas_attention=True)
    model = build_model(cfg)
    return model, make_weights(model, key)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_reference_draws_the_served_weights(family):
    """Layer by layer, the reference's weights are the served registry's,
    bit for bit."""
    config = _config(family)
    key = jax.random.key(7)
    _, served = _program(config, key)
    same = lambda a, b: np.testing.assert_array_equal(  # noqa: E731
        np.asarray(a, np.float32), np.asarray(b, np.float32))
    for layer in range(config["num_hidden_layers"]):
        ref = dense_gqa.layer_weights(config, key, layer)
        assert jax.tree.structure(ref) == jax.tree.structure(
            served["blocks"])
        for a, b in zip(jax.tree.leaves(ref),
                        jax.tree.leaves(served["blocks"])):
            assert a.dtype == b.dtype == jnp.bfloat16
            same(a, b[layer])
    same(dense_gqa.embed_weights(config, key), served["embed"])
    head = served["embed"].T if config["tie_word_embeddings"] \
        else served["lm_head"]
    same(dense_gqa.head_weights(config, key), head)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_checksums_cover_every_served_leaf(family):
    """The harness checksums every leaf the program serves, and the
    reference gives each of them, bit for bit."""
    from bench.harness.main import _checksums
    config = _config(family)
    key = jax.random.key(9)
    _, served = _program(config, key)
    sums = _checksums(served)
    assert len(sums) == len(jax.tree.leaves(served))
    assert "final_norm" in sums
    assert sums == dense_gqa.checksums(config, key)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_reference_matches_prefill_then_paged_decode(family):
    config = _config(family)
    key = jax.random.key(3)
    model, params = _program(config, key)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    bs, nb, prompt_len, steps = 4, 8, 11, 5
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, config["vocab_size"], prompt_len + steps)
    cache = model.init_paged_cache(nb + 1, bs, jnp.float32)
    table = jnp.arange(nb, dtype=jnp.int32)[None]
    logits, cache = model.prefill_chunk_paged(
        params32, cache, jnp.asarray(tokens[None, :prompt_len], jnp.int32),
        jnp.zeros(1, jnp.int32), jnp.full(1, prompt_len, jnp.int32), table)
    got = [np.asarray(logits[0])]
    for t in range(steps - 1):
        pos = prompt_len + t
        logits, cache = model.decode_step_paged(
            params32, cache, jnp.asarray(tokens[pos:pos + 1], jnp.int32),
            jnp.full(1, pos, jnp.int32), table)
        got.append(np.asarray(logits[0]))
    got = np.stack(got)[:, :config["vocab_size"]]
    seq = tokens[:prompt_len + steps - 1]
    read = np.arange(prompt_len - 1, prompt_len + steps - 1)
    # a second, shorter sequence in the same call: sequences do not mix
    want = dense_gqa.logits(config, key, [seq, seq[:prompt_len]],
                            [read, read[:1]])["reference"]
    np.testing.assert_allclose(got, want[0], atol=ATOL, rtol=0)
    np.testing.assert_allclose(got[:1], want[1], atol=ATOL, rtol=0)


def test_the_controls_rounding_is_float8_e4m3():
    """The control rounds to float8 e4m3 exactly as a cast does, at every
    representable value, every tie between two of them, and a spread of
    magnitudes from the subnormals to the clip at 448."""
    import ml_dtypes
    rng = np.random.default_rng(0)
    e = np.arange(256, dtype=np.uint8).view(ml_dtypes.float8_e4m3fn) \
        .astype(np.float32)
    e = np.sort(e[np.isfinite(e)])
    x = np.concatenate([rng.normal(0.0, s, 20000)
                        for s in (1e-3, 1e-2, 1e-1, 1.0, 30.0)]
                       + [e, (e[1:] + e[:-1]) / 2]).astype(np.float32)
    want = x.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    got = np.asarray(jax.jit(dense_gqa.round_e4m3)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)
