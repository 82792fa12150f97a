"""launch/serve.py plumbing: compile-cache placement, argument defaults at
published width, and one threaded serve through build_registry /
build_cluster / drive_threaded at the reduced size."""
import time

import jax
import pytest

from repro.launch import compile_cache, serve

ARCH = "granite-3-2b"


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_follows_env_else_fixed_checkout_path(
        monkeypatch, tmp_path, env_dir):
    saved = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(compile_cache.DEFAULT_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    try:
        assert compile_cache.enable_compile_cache() == want
        # with the variable set, JAX reads it itself: nothing is overridden
        assert jax.config.jax_compilation_cache_dir == \
            (saved if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_default_sits_in_the_checkout():
    root = compile_cache.DEFAULT_DIR.parent
    assert (root / "chip_smoke.py").exists() and (root / "src").is_dir()


@pytest.mark.parametrize("argv,want", [([], 2048), (["--reduced"], 128),
                                       (["--max-seq-len", "512"], 512)])
def test_serve_defaults_to_published_width(argv, want):
    args = serve.parse_args(argv)
    assert args.max_seq_len == want
    assert args.reduced == ("--reduced" in argv)
    assert serve.serving_dtype(args.reduced) == \
        (jax.numpy.float32 if args.reduced else jax.numpy.bfloat16)


def test_threaded_serve_reduced_end_to_end():
    args = serve.parse_args([
        "--reduced", "--arch", ARCH, "--instances", "2", "--slots", "2",
        "--max-seq-len", "64", "--backend", "paged-xla", "--decode-burst",
        "4", "--requests", "4", "--rate", "1000", "--prompt-len", "20", "40",
        "--max-new-tokens", "3", "--threaded", "--max-wall", "120"])
    registry = serve.build_registry([ARCH], jax.random.key(0), reduced=True)
    engines, agents, _, controller = serve.build_cluster(args, registry,
                                                         [ARCH])
    inputs = [_record_step_inputs(eng) for eng in engines]
    reqs = serve.build_workload(args, [ARCH], time.monotonic())
    stats = serve.drive_threaded(engines, agents, controller, reqs,
                                 max_wall=args.max_wall)
    assert stats["served"] == 4 and stats["failed"] == 0
    assert all(len(r.output_tokens) == 3 for r in reqs)
    assert all(20 <= r.prompt_len < 40 for r in reqs)
    assert sum(eng.stats.decode_bursts for eng in engines) > 0
    # the pool follows the params' device and dtype, and every step input
    # is committed to that device (not left on JAX's default device)
    for eng, seen in zip(engines, inputs):
        leaf = jax.tree.leaves(eng.cache)[0]
        assert leaf.dtype == jax.numpy.float32
        assert leaf.devices() == jax.tree.leaves(eng.params)[0].devices()
        assert seen and seen == {(True, eng.device)}


def _record_step_inputs(eng) -> set:
    """Wrap the engine's jitted steps; every dispatch adds (committed,
    device) of each array input to the returned set."""
    seen = set()
    for name in ("_decode_fn", "_chunk_fn", "_burst_fn"):
        def recorded(*args, _fn=getattr(eng, name)):
            seen.update((a.committed, d) for a in jax.tree.leaves(args)
                        if isinstance(a, jax.Array) for d in a.devices())
            return _fn(*args)
        setattr(eng, name, recorded)
    return seen
