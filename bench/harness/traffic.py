"""One general generator for every traffic mix.

A mix is a data file under ``bench/traffic/``: an arrival rate, the class
mix with each class's limits, lognormal prompt and output lengths, and
the seed of its sizes.  The generator is copied from the program's
``data/workload.py`` and ``data/sharegpt_synth.py`` (Poisson / gamma
arrivals, clipped lognormal lengths, the W_A / W_C class shares), so that
a change to the program cannot move the yardstick.

Every run of a mix at one length gets the same schedule: due times,
classes, prompt and output lengths, all drawn from the mix's own
``sizes_seed``.  The run's ``--seed`` draws the prompt tokens (and, in the
harness, the weights).  The work offered in a window is then the same for
every seed, in the same order: with a request holding a slot for tens of
seconds, the order alone moves the tokens served in a window by a fifth.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    idx: int
    due_s: float          # seconds after the window opens
    slo_class: str
    ttft_s: float         # this class's TTFT limit
    tpot_s: float | None  # this class's time-per-output-token limit
    prompt: List[int]
    max_new_tokens: int


def _lognormal(rng: np.random.Generator, spec: Dict[str, Any],
               n: int) -> np.ndarray:
    x = rng.lognormal(spec["lognormal_mu"], spec["lognormal_sigma"], n)
    return np.clip(x, spec["min"], spec["max"]).astype(int)


def _gaps(rng: np.random.Generator, n: int, rate: float,
          cv: float) -> np.ndarray:
    if cv <= 1.0:
        return rng.exponential(1.0 / rate, n)
    shape = 1.0 / (cv * cv)  # gamma inter-arrivals with CV > 1: bursty
    return rng.gamma(shape, 1.0 / (rate * shape), n)


def _class_counts(classes: List[Dict[str, Any]], n: int) -> List[int]:
    """Exact per-class counts for ``n`` requests (largest remainders)."""
    shares = np.array([c["share"] for c in classes], float)
    want = shares / shares.sum() * n
    counts = np.floor(want).astype(int)
    for i in np.argsort(-(want - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def request_count(traffic: Dict[str, Any], seconds: float) -> int:
    return max(1, int(round(traffic["rate_per_s"] * seconds)))


def generate(traffic: Dict[str, Any], *, seconds: float, seed: int,
             max_seq_len: int, vocab_size: int) -> List[Arrival]:
    """The arrivals due in a window of ``seconds``, in due order."""
    n = request_count(traffic, seconds)
    sizes = np.random.default_rng(traffic["sizes_seed"])
    prompts = _lognormal(sizes, traffic["prompt_tokens"], n)
    outputs = _lognormal(sizes, traffic["output_tokens"], n)
    prompts = np.minimum(prompts, max_seq_len - 1)
    outputs = np.maximum(np.minimum(outputs, max_seq_len - prompts), 1)
    classes = traffic["classes"]
    labels = np.repeat(np.arange(len(classes)),
                       _class_counts(classes, n))
    gaps = _gaps(sizes, n, traffic["rate_per_s"],
                 traffic.get("arrival_cv", 1.0))
    gaps *= seconds / gaps.sum()  # the n arrivals span the window exactly
    order = sizes.permutation(n)  # the classes interleaved
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])

    run = np.random.default_rng(seed)
    out = []
    for i in range(n):
        j = order[i]
        c = classes[labels[j]]
        prompt = run.integers(0, vocab_size, int(prompts[j])).tolist()
        out.append(Arrival(idx=i, due_s=float(due[i]), slo_class=c["name"],
                           ttft_s=float(c["ttft_s"]),
                           tpot_s=c.get("tpot_s"), prompt=prompt,
                           max_new_tokens=int(outputs[j])))
    return out
