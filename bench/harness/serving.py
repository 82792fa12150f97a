"""The served path under the benchmark's open-loop client.

Set-up builds what ``launch/serve.py`` builds: the weights on the device
from the seed (the model's initialiser, served in bf16: ``make_weights``),
then ``build_cluster`` (engines,
``QLMAgent``s, the ``QLMController`` and its calibration engine).  It then
warms every shape the window uses on each serving engine.

The window runs the cluster on ``serving/cluster.ThreadedCluster``; the
client submits each request through ``controller.submit`` at its due time.
After every agent round (``ThreadedCluster.round_hook``, on the agent's
own thread) the client stamps the tokens each request has received, notes
the first round each request sits in a slot, and records the work the
round computed: the prompt spans prefilled and the context of each token
decoded.  Host spans (``jax.profiler.TraceAnnotation``) mark the
benchmark's calls into the layers: ``agent.run_iteration``,
``controller.tick`` and ``client.submit``.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import traffic as traffic_lib
from bench.harness.stats import Record

# prompt lengths whose prefill chunks cover the engine's chunk buckets
# (16, 32, 64 and 128 tokens: 10, 30 and 60 tokens fall in the first three,
# 200 tokens run a 128-token chunk and a 72-token chunk in the 128 bucket)
WARM_PROMPTS = (10, 30, 60, 200)
WARM_NEW_TOKENS = 9  # a chunk round with a single step, then bursts


@functools.partial(jax.jit, static_argnums=0)
def make_weights(model, key):
    """The served weights from the run's key, in one call on the device:
    the program's initialiser drawn in float32 and rounded to bf16.

    Drawn in bf16 itself, ``jax.random``'s normal and truncated normal
    come out with a mean near -0.015 of their scale.  Through 2048- and
    8192-wide layers that common part grows from layer to layer until the
    last hidden state points the same way at every position of every
    prompt: the greedy tokens then hardly depend on the prompt or the KV
    cache, and the comparison with the reference could not see a fault
    there."""
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                        model.init(key, jnp.float32))


@dataclasses.dataclass
class RoundWork:
    """Work one agent round computed, from live lengths."""
    prefill_spans: List[Tuple[int, int]]   # [start, end) prompt positions
    decode_contexts: List[int]             # keys attended per decoded token
    produced: int                          # tokens produced (first + decoded)


class Served:
    """A built cluster and the client's view of one window."""

    def __init__(self, config: Dict[str, Any], key, *, seed: int):
        from bench.harness.spec import model_config
        from repro.launch import serve
        from repro.models import build_model

        self.config = config
        self.name = config["name"]
        e = config["engine"]
        cfg = model_config(config)
        self.model_cfg = cfg
        model = build_model(cfg)
        params = make_weights(model, key)
        jax.block_until_ready(params)
        self.registry = {self.name: (model, params)}
        args = serve.parse_args([
            "--arch", self.name, "--instances", "1",
            "--backend", e["backend"], "--decode-burst",
            str(e["decode_burst"]),
            "--prefix-sharing" if e["prefix_sharing"]
            else "--no-prefix-sharing",
            "--slots", str(e["slots"]), "--max-seq-len",
            str(e["max_seq_len"]), "--threaded", "--seed", str(seed)])
        # the calibration engine draws its prompts from numpy's global
        # generator: seed it, so calibration does the same work every run
        np.random.seed(seed % 2**32)
        self.engines, self.agents, _, self.controller = serve.build_cluster(
            args, self.registry, [self.name])
        for eng in self.engines:
            if (eng.cfg.block_size, eng.cfg.prefill_chunk_tokens) != (
                    e["block_size"], e["prefill_chunk_tokens"]):
                raise RuntimeError("engine block size or chunk differs from "
                                   "the configuration file")
        self.records: Dict[int, Record] = {}
        self.round_work: List[RoundWork] = []
        self.tick_seconds: List[float] = []
        # rounds that start and end inside [count_from, count_until] have
        # their work recorded (the traced part of the window)
        self.count_from = self.count_until = None
        self._round_start = [0.0] * len(self.engines)
        self._seen: Dict[int, Tuple[int, int]] = {}   # req_id -> (pf, gen)
        self._done_idx = [0] * len(self.engines)

    # -- set-up --------------------------------------------------------------
    def warm_up(self) -> None:
        """Run every shape the window uses through each serving engine:
        each prefill-chunk bucket, the single decode step and the burst."""
        from repro.core.request import make_request
        rng = np.random.default_rng(0)
        vocab = self.model_cfg.vocab_size
        for eng in self.engines:
            for n in WARM_PROMPTS:
                n = min(n, eng.cfg.max_seq_len - WARM_NEW_TOKENS - 1)
                r = make_request(rng.integers(0, vocab, n).tolist(),
                                 self.name, "batch2",
                                 arrival_time=time.monotonic(),
                                 max_new_tokens=WARM_NEW_TOKENS)
                if not eng.admit(r):
                    raise RuntimeError("warm-up request not admitted")
                while not r.finished():
                    eng.steps()
            self._done_idx[self.engines.index(eng)] = len(eng.completed)

    # -- the window ----------------------------------------------------------
    def _hook(self, idx: int) -> None:
        now = time.monotonic()
        eng = self.engines[idx]
        done = eng.completed[self._done_idx[idx]:]
        self._done_idx[idx] += len(done)
        live = [(r, int(eng.prefill_pos[i]), i)
                for i, r in enumerate(eng.slots) if r is not None]
        live += [(r, r.prompt_len, None) for r in done]
        work = RoundWork([], [], 0)
        for r, pos, slot in live:
            rec = self.records.get(r.req_id)
            if rec is None:
                continue
            if rec.admitted is None:
                rec.admitted = now
            if rec.slot is None and slot is not None:
                rec.slot = (idx, slot)
            n = len(r.output_tokens)
            if not rec.stamps or rec.stamps[-1][1] != n:
                if n > 0:
                    rec.stamps.append((now, n))
            pf, gen = self._seen.get(r.req_id, (r.prefix_shared_tokens, 0))
            if pos > pf:
                work.prefill_spans.append((pf, pos))
            first_here = gen == 0 and n > 0 and pos >= r.prompt_len > pf
            for g in range(gen + 1, n + 1):
                work.produced += 1
                if g == 1 and first_here:
                    continue  # produced by the prompt's last chunk
                # the g-th token comes from a decode step at cache length
                # prompt_len + g - 2, attending that many keys plus its own
                work.decode_contexts.append(r.prompt_len + g - 1)
            self._seen[r.req_id] = (max(pf, pos), n)
        if self.count_from is not None \
                and self._round_start[idx] >= self.count_from \
                and (self.count_until is None or now <= self.count_until) \
                and (work.prefill_spans or work.decode_contexts):
            self.round_work.append(work)

    def _instrument(self) -> None:
        for idx, agent in enumerate(self.agents):
            inner = agent.run_iteration

            def run_iteration(_inner=inner, _idx=idx):
                self._round_start[_idx] = time.monotonic()
                with jax.profiler.TraceAnnotation("agent.run_iteration"):
                    return _inner()
            agent.run_iteration = run_iteration
        tick = self.controller.tick

        def timed_tick(now):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("controller.tick"):
                out = tick(now)
            self.tick_seconds.append(time.perf_counter() - t0)
            return out
        self.controller.tick = timed_tick

    def run_window(self, arrivals: List[traffic_lib.Arrival], *,
                   seconds: float, drain_s: float,
                   trace_dir: Optional[str] = None,
                   trace_seconds: float = 10.0) -> Dict[str, float]:
        """Serve ``arrivals`` open-loop over a window of ``seconds`` and a
        drain of ``drain_s``.  With ``trace_dir`` the profiler records
        ``trace_seconds`` from the middle of the window, on a thread of its
        own, and the work of the rounds inside that span is recorded.
        Returns the window's bounds on the host's monotonic clock."""
        from repro.core.request import make_request
        from repro.serving import ThreadedCluster

        self._instrument()
        cluster = ThreadedCluster(self.controller, self.agents, self.engines)
        cluster.round_hook = self._hook
        reqs = []
        tracer = None
        bounds: Dict[str, float] = {}
        cluster.start()
        try:
            t0 = time.monotonic() + 0.05
            t1 = t0 + seconds
            if trace_dir is not None:
                span = min(trace_seconds, seconds)
                tracer = threading.Thread(
                    target=self._trace, name="bench-profiler",
                    args=(trace_dir, t0 + (seconds - span) / 2, span, bounds))
                tracer.start()
            else:
                self.count_from = t0
                self.count_until = t1
            for a in arrivals:
                due = t0 + a.due_s
                time.sleep(max(0.0, due - time.monotonic()))
                r = make_request(a.prompt, self.name, a.slo_class,
                                 arrival_time=due,
                                 max_new_tokens=a.max_new_tokens)
                r.slo = a.ttft_s
                rec = Record(idx=a.idx, slo_class=a.slo_class,
                             ttft_limit_s=a.ttft_s, tpot_limit_s=a.tpot_s,
                             due=due, prompt_len=len(a.prompt),
                             max_new_tokens=a.max_new_tokens)
                self.records[r.req_id] = rec
                rec.submitted = time.monotonic()
                with jax.profiler.TraceAnnotation("client.submit"):
                    self.controller.submit(r, rec.submitted)
                reqs.append(r)
            time.sleep(max(0.0, t1 - time.monotonic()))
            t2 = t1 + drain_s
            time.sleep(max(0.0, t2 - time.monotonic()))
        finally:
            if tracer is not None:
                tracer.join()
            cluster.stop()
        self.requests = reqs
        for r in reqs:
            rec = self.records[r.req_id]
            rec.failed = bool(r.failed or r.rejected or r.dropped())
        self.rounds = list(cluster.rounds)
        return dict(bounds, start=t0, end=t1, drain_end=t2)

    def _trace(self, trace_dir: str, at: float, span: float,
               bounds: Dict[str, float]) -> None:
        """Profile ``span`` seconds from ``at``; count the work of the
        rounds that start after the trace started and end before it
        stops (the trace may hold a little more, never less)."""
        time.sleep(max(0.0, at - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.count_from = bounds["trace_start"] = time.monotonic()
        time.sleep(max(0.0, self.count_from + span - time.monotonic()))
        self.count_until = bounds["trace_stop"] = time.monotonic()
        jax.profiler.stop_trace()

    def release(self) -> None:
        """Free the program's device state: every KV pool and the weights."""
        for eng in self.engines:
            if eng.cache is not None:
                eng.release_cache()
        for _, params in self.registry.values():
            for leaf in jax.tree.leaves(params):
                leaf.delete()
        self.registry.clear()
        for agent in self.agents:
            agent.registry = {}
        for eng in self.engines:
            eng.params = None


def served_program_check(engine) -> Dict[str, int]:
    """The engine's decode, prefill-chunk and burst programs, compiled for
    the shapes they serve: each must hold a Mosaic kernel
    (``tpu_custom_call``).  Returns each program's temporary bytes as the
    chip's compiler reports them."""
    spec = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        t)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    B, nb = engine.cfg.max_slots, engine.cfg.max_blocks_per_seq()
    C = engine.cfg.prefill_chunk_tokens
    params, cache = spec(engine.params), spec(engine.cache)
    lowered = {
        "decode": engine._decode_fn.lower(params, cache, i32(B), i32(B),
                                          i32(B, nb)),
        "prefill_chunk": engine._chunk_fn.lower(params, cache, i32(B, C),
                                                i32(B), i32(B), i32(B, nb)),
        "burst": engine._burst_fn.lower(params, cache, i32(B), i32(B), i32(B),
                                        jax.ShapeDtypeStruct((B,), jnp.bool_),
                                        i32(), i32(B, nb)),
    }
    temps = {}
    for name, low in lowered.items():
        compiled = low.compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise RuntimeError(f"served {name} program holds no "
                               "tpu_custom_call: its kernel was interpreted")
        temps[name] = compiled.memory_analysis().temp_size_in_bytes
    return temps
