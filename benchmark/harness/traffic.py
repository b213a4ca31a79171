"""The one traffic generator.  A mix is a data file of parameters.

Every run of a mix replays ONE trace: the request sizes and the gaps between
arrivals are drawn once (generator seed 0) and every ``--seed`` meets them
in the same order; the seed changes the token ids (and the weights).  So
two seeds never differ in how much work the window holds, nor in which
long prompt meets which: near the knee that decides the tail, and six
orders of one multiset spread ``ttft_p90_ms`` by 17-20% where two runs of
one order differ by about 2% (my chip runs, PR 23).

Open loop (``"loop": "open"``): Poisson arrivals on a schedule fixed before
the run, whether or not earlier requests have finished.  The number of
arrivals is ``round(rate * span)`` and the gaps are scaled to fill the span
exactly.

Closed loop (``"loop": "closed"``): ``clients`` callers, each sending its
next request when the last is answered.  The requests come from a pool of
``pool`` sizes that is cycled, so a window of any length sees the same
sizes over and over.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class Request:
    index: int
    prompt: np.ndarray            # [L] int64 token ids
    n_out: int                    # tokens to generate (greedy, no EOS)
    due: Optional[float] = None   # seconds after the run's start (open loop)
    client: int = -1              # closed loop: who sends it


@dataclass
class Plan:
    loop: str                     # "open" | "closed"
    warmup_s: float
    seconds: float
    requests: List[Request] = field(default_factory=list)
    clients: int = 0              # closed loop


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def make_plan(traffic: dict, deploy: dict, vocab_size: int, seed: int,
              seconds: float) -> Plan:
    """The run's requests.  ``traffic`` is the mix's file, ``deploy`` the
    cell's (its ``rate_per_s`` or ``clients``)."""
    shape_rng = np.random.default_rng(0)         # the one trace
    # a shuffle of independent draws changes nothing in kind; it stays
    # because the cells' numbers were measured on this arrangement
    order_rng = np.random.default_rng([0, 1])
    token_rng = np.random.default_rng([int(seed), 2])
    warmup_s = float(traffic["warmup_s"])
    span = warmup_s + float(seconds)
    loop = traffic["loop"]
    if loop == "open":
        n = max(1, int(round(float(deploy["rate_per_s"]) * span)))
    elif loop == "closed":
        n = int(traffic["pool"])
    else:
        raise ValueError(f"unknown loop kind {loop!r}")
    prompt_len = _lengths(traffic["prompt_tokens"], n, shape_rng)
    out_len = _lengths(traffic["output_tokens"], n, shape_rng)
    perm = order_rng.permutation(n)
    prompt_len, out_len = prompt_len[perm], out_len[perm]
    plan = Plan(loop=loop, warmup_s=warmup_s, seconds=float(seconds))
    if loop == "open":
        gaps = shape_rng.exponential(1.0, n)[order_rng.permutation(n)]
        # the first arrival at 0, the last one gap before the span's end
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        due *= span / float(np.sum(gaps))
    else:
        due = [None] * n
        plan.clients = int(deploy["clients"])
    for i in range(n):
        prompt = token_rng.integers(1, vocab_size, int(prompt_len[i]),
                                    dtype=np.int64)
        plan.requests.append(Request(
            index=i, prompt=prompt, n_out=int(out_len[i]),
            due=None if due[i] is None else float(due[i])))
    return plan
