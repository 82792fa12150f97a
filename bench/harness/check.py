"""Decide ``correct``: what the timed path served, against the plain
reference.

Once the window has closed and the program's device state is freed, a
sample of the requests the window finished is drawn from the seed, the
longest among them.  The reference (``bench/reference/<name>.py``, float32
at ``Precision.HIGHEST``, weights drawn again from the seed) runs once
over each sampled prompt with its served tokens, and at every served
token reads how far that token's logit lies below the reference's best.
The number compared is the widest such gap over the sample.

Under greedy decoding a served token is the program's argmax; bf16
rounding can only pick a token whose reference logit is within rounding
of the best, and a wrong kernel, cache or token picks one far below it.

The control (``bench/control.py``) puts the reference in the program's
place computed one precision step below the configuration's bf16 (float8
e4m3 weights and matmul inputs, float32 sums): at each position the token
that precision puts first stands in for the served one, and goes through
the same comparison and the same limit.
"""
from __future__ import annotations

import importlib
import json
import pathlib
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

LIMITS_DIR = pathlib.Path(__file__).resolve().parents[1] / "limits"


def limits(cell: str) -> Dict[str, Any]:
    path = LIMITS_DIR / f"{cell}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no limits for cell {cell!r} at {path}")
    return json.loads(path.read_text())


def reference_module(config: Dict[str, Any]):
    return importlib.import_module(f"bench.reference.{config['reference']}")


def sample(finished: Sequence[Any], seed: int, served_tokens: int,
           slot_of: Optional[Dict[int, Any]] = None) -> List:
    """The longest finished request (prompt plus served tokens); then, in
    an order drawn from the seed, one request from every slot the window
    served from; then others until the sample holds ``served_tokens``
    served tokens."""
    pool = sorted((r for r in finished if len(r.output_tokens) > 0),
                  key=lambda r: r.req_id)
    if not pool:
        return []
    slot_of = slot_of or {}
    longest = max(pool, key=lambda r: (r.prompt_len + len(r.output_tokens),
                                       r.req_id))
    rng = np.random.default_rng([seed, 1])
    order = [pool[i] for i in rng.permutation(len(pool))]
    out = [longest]
    slots = {slot_of.get(longest.req_id)}
    for r in order:
        s = slot_of.get(r.req_id)
        if s is not None and s not in slots:
            out.append(r)
            slots.add(s)
    total = sum(len(r.output_tokens) for r in out)
    for r in order:
        if total >= served_tokens:
            break
        if all(r is not o for o in out):
            out.append(r)
            total += len(r.output_tokens)
    return out


def widest_gap(ref, config, key, reqs, control: bool = False
               ) -> Dict[str, Any]:
    """Over the sampled requests, the widest gap between the reference's
    best logit and the logit of the served token ("served") and, with
    ``control``, of the token that the fp8 control puts first in the
    served token's place ("control")."""
    seqs = [list(r.prompt_tokens) + list(r.output_tokens[:-1]) for r in reqs]
    reads = [np.arange(r.prompt_len - 1, len(s)) for r, s in zip(reqs, seqs)]
    got = ref.logits(config, key, seqs, reads, control=control)
    out: Dict[str, Any] = {}
    for i, r in enumerate(reqs):
        want = got["reference"][i]
        rows = np.arange(len(want))
        best = want.max(axis=1)
        gaps = {"served": best - want[rows, np.asarray(r.output_tokens)]}
        if control:
            gaps["control"] = best - want[rows,
                                          got["control"][i].argmax(axis=1)]
        for k, g in gaps.items():
            if g.max() > out.get(k, -np.inf):
                out[k] = float(g.max())
                if k == "served":
                    j = int(g.argmax())
                    order = np.sort(want[j])[::-1]
                    rank = int((want[j] > want[j, r.output_tokens[j]]).sum())
                    worst = (f"request of {r.prompt_len} prompt tokens, "
                             f"served token {j} of {len(r.output_tokens)}, "
                             f"ranked {rank} by the reference, whose first "
                             f"two differ by {order[0] - order[1]:.4f}")
            out[f"{k}.mismatches"] = out.get(f"{k}.mismatches", 0) \
                + int((g > 0).sum())
    out["served.worst"] = worst
    return out


def decide(checks: Dict[str, Dict[str, float]]) -> bool:
    """``correct``: every number compared is at or under its limit."""
    return bool(checks) and all(c["value"] <= c["limit"]
                                for c in checks.values())
