"""The generator: the same seed gives the same requests; every seed gets
the one trace of sizes and arrivals, with token ids of its own."""
import json
import os

import numpy as np

from harness import traffic
from tinytree import BENCH


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _plan(seed, seconds=30.0):
    return traffic.make_plan(_mix("chat"), {"rate_per_s": 2.5}, 32768, seed,
                             seconds)


def test_same_seed_same_schedule():
    a, b = _plan(2 ** 31 + 11), _plan(2 ** 31 + 11)
    assert [r.due for r in a.requests] == [r.due for r in b.requests]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, b.requests))


def test_every_seed_replays_one_trace():
    a, b = _plan(1), _plan(2)
    assert len(a.requests) == round(2.5 * (30 + a.warmup_s))
    assert [r.due for r in a.requests] == [r.due for r in b.requests]
    assert [(len(r.prompt), r.n_out) for r in a.requests] == \
        [(len(r.prompt), r.n_out) for r in b.requests]
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, b.requests))


def test_arrivals_fill_the_span_and_lengths_are_clipped():
    p = _plan(3)
    due = [r.due for r in p.requests]
    assert due[0] == 0.0 and due == sorted(due)
    assert due[-1] < p.warmup_s + p.seconds
    mix = _mix("chat")
    assert all(mix["prompt_tokens"]["min"] <= len(r.prompt)
               <= mix["prompt_tokens"]["max"] for r in p.requests)
    assert all(mix["output_tokens"]["min"] <= r.n_out
               <= mix["output_tokens"]["max"] for r in p.requests)


def test_closed_loop_pool():
    p = traffic.make_plan(_mix("docs"), {"clients": 16}, 32000, 9, 30.0)
    assert p.loop == "closed" and p.clients == 16
    assert len(p.requests) == _mix("docs")["pool"]
    assert all(r.due is None for r in p.requests)
