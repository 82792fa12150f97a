"""Compile the serving path for one described TPU v5e chip (no chip needed).

The TPU compiler is installed with jax, and it compiles for a topology that
is described, not attached.  These tests compile the paged Pallas kernels
(the decode kernel at granite-3-2b's and deepseek-67b's widths, one per
route) and the full-width granite-3-2b bf16 decode and prefill-chunk steps
for one v5e chip and check what only that compiler can show: that the kernels
lower to Mosaic (``tpu_custom_call``) rather than interpreted HLO, and that
the steps fit the chip's 16 GiB of HBM.  Nothing runs; a compile that
passes is not a chip run.

The topology is described inside fixtures, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.  This is the only file that does it.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops
from repro.models import build_model

V5E_HBM_BYTES = 16 * 2**30
# granite-3-2b serving shapes: 16 slots, 2048-token sequences in 16-token
# pages, a 2048-block pool
SLOTS, BLOCK, MAX_SEQ, POOL_BLOCKS, CHUNK = 16, 16, 2048, 2048, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around them."""
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def granite():
    return get_arch("granite-3-2b")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_inputs(cfg, sharding, max_seq=MAX_SEQ, pool_blocks=POOL_BLOCKS):
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pages = _spec((pool_blocks, KVH, BLOCK, D), jnp.bfloat16, sharding)
    table = _spec((SLOTS, max_seq // BLOCK), jnp.int32, sharding)
    per_seq = _spec((SLOTS,), jnp.int32, sharding)
    return H, KVH, D, pages, table, per_seq


@pytest.mark.parametrize("arch,max_seq,pool_blocks", [
    ("granite-3-2b", MAX_SEQ, POOL_BLOCKS),  # head_dim 64, 128-page table
    ("deepseek-67b", 4096, 4096),            # head_dim 128, 256-page table
])
def test_paged_decode_kernel_lowers_to_mosaic(arch, max_seq, pool_blocks,
                                              one_chip, no_persistent_cache):
    cfg = get_arch(arch)
    H, KVH, D, pages, table, lengths = _kernel_inputs(
        cfg, one_chip, max_seq=max_seq, pool_blocks=pool_blocks)
    q = _spec((SLOTS, H, D), jnp.bfloat16, one_chip)
    text = ops.paged_decode_attention.lower(
        q, pages, pages, table, lengths, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_prefill_kernel_lowers_to_mosaic(granite, one_chip,
                                               no_persistent_cache):
    H, KVH, D, pages, table, per_seq = _kernel_inputs(granite, one_chip)
    q = _spec((SLOTS, H, CHUNK, D), jnp.bfloat16, one_chip)
    chunk_kv = _spec((SLOTS, KVH, CHUNK, D), jnp.bfloat16, one_chip)
    text = ops.paged_prefill_attention.lower(
        q, pages, pages, chunk_kv, chunk_kv, table, per_seq, per_seq,
        interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.fixture
def mosaic_kernels(monkeypatch):
    """Steer the kernels' interpret choice off: on this CPU host
    ``default_interpret`` is True, and a step traced here would carry
    interpreted kernels into the TPU program.  Traces cached under the
    CPU choice are dropped first."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_full_width_step_fits_one_chip(granite, one_chip, no_persistent_cache,
                                       mosaic_kernels, step):
    cfg = dataclasses.replace(granite, use_pallas_attention=True)
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    params = place(jax.eval_shape(lambda k: model.init(k, jnp.bfloat16),
                                  jax.random.key(0)))
    pool = place(jax.eval_shape(lambda: model.init_paged_cache(
        POOL_BLOCKS, BLOCK, jnp.bfloat16)))
    table = _spec((SLOTS, MAX_SEQ // BLOCK), jnp.int32, one_chip)
    per_seq = _spec((SLOTS,), jnp.int32, one_chip)
    if step == "decode":
        fn, data = model.decode_step_paged, (per_seq, per_seq, table)
    else:
        tokens = _spec((SLOTS, CHUNK), jnp.int32, one_chip)
        fn, data = model.prefill_chunk_paged, (tokens, per_seq, per_seq,
                                               table)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *data).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's instruction carries its name (``<kernel>`` or
    # ``<kernel>.<n>``): the profiler's "XLA Ops" name it so on the chip
    kernel = {"decode": "paged_decode_attention",
              "prefill_chunk": "paged_prefill_attention"}[step]
    assert re.search(rf"^\s*%{kernel}(\.\d+)? = .*custom-call\(",
                     compiled.as_text(), re.M), kernel
    mem = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES, mem
    # the pool is updated in place: no second pool among the temporaries
    assert mem.temp_size_in_bytes < pool_bytes, mem
