"""Decides ``correct`` for a serving cell.

Once the window has closed and the program's state is freed, a sample of
the requests it finished (drawn from the seed, the longest always in it)
goes through the plain reference, one forward over each prompt with its
served tokens.  The numbers compared are the widest and the mean gap by
which a served token's reference logit lies below the reference's best at
that position: 0 where the program chose what the reference chooses.

The control is the reference itself computed in a lower precision and put
in the program's place: at each of the same positions, the gap of the
token that the lower-precision forward puts first goes through the same
comparison.  ``tools/control.py`` sets ``IN_PROGRAMS_PLACE``; a benchmark
run never does.
"""
from __future__ import annotations

import time

import numpy as np

from . import weights
from .spec import reference_module

PAD_TO = 1024       # sequences are padded to a multiple: few programs
IN_PROGRAMS_PLACE = None    # None | "int8" (the control) | "bf16" (a witness)


def pick_sample(tracks, seed: int, n: int):
    """``n`` finished requests: the longest, and others drawn from the
    seed."""
    done = [t for t in tracks if t.measured and t.done
            and not t.handle.truncated and len(t.handle.output_ids)]
    if not done:
        return []
    done.sort(key=lambda t: t.req.index)
    longest = max(done, key=lambda t: len(t.req.prompt) + t.req.n_out)
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([int(seed), 3])
    take = min(n - 1, len(rest))
    picked = [rest[i] for i in rng.choice(len(rest), take, replace=False)]
    return [longest] + picked


def _max_mean(gaps):
    if not gaps.size:
        return {"logit_gap_max": float("inf"), "logit_gap_mean": float("inf")}
    return {"logit_gap_max": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean())}


def served_gap(config: dict, seed: int, samples, margin_min: float = 0.0,
               control=None):
    """``samples``: [(prompt ids, served ids)].  Returns the program's
    readings (``"program"``), what they were taken over and, with
    ``control`` (a precision of the reference's ``mm``), that forward's
    readings at the same positions (``"control"``).

    A sparse model's router decides some tokens' experts by less than
    rounding.  The program, in the precision the configuration states, may
    decide those otherwise, and then serves a token the reference does
    not, however sound it is.  So the gaps are read over the DECIDED
    positions: those whose router margin (the reference's own) is at least
    ``margin_min`` in every layer.  The share left out is reported and
    held to a cap."""
    import jax
    import jax.numpy as jnp

    ref = reference_module(config["family"])
    dtype = config["dtype"]
    layer = jax.jit(lambda x, w: ref.layer(x, w, config))
    layer_c = jax.jit(lambda x, w: ref.layer(x, w, config, control))
    top = weights.make_group(seed, weights.TOP, ref.top_shapes(config),
                             dtype)
    xs, rows, served = [], [], []
    for prompt, out in samples:
        ids = np.concatenate([np.asarray(prompt, np.int64),
                              np.asarray(out[:-1], np.int64)])
        first = len(prompt) - 1
        rows.append(np.arange(first, first + len(out)))
        served.append(np.asarray(out, np.int64))
        ids = np.pad(ids, (0, -len(ids) % PAD_TO))
        xs.append(ref.embed(jnp.asarray(ids), top))
    xc = list(xs) if control else None
    margins = [np.full(len(r), np.inf) for r in rows]
    flipped = [np.zeros(len(r), bool) for r in rows]
    shapes = ref.layer_shapes(config)
    t0 = time.perf_counter()
    for li in range(int(config["num_hidden_layers"])):
        w = weights.make_group(seed, li, shapes, dtype)
        for i, x in enumerate(xs):
            xs[i], route = layer(x, w)
            if route is not None:
                margins[i] = np.minimum(margins[i],
                                        np.asarray(route[0])[rows[i]])
            if control:
                xc[i], route_c = layer_c(xc[i], w)
                if route is not None:
                    flipped[i] |= np.any(
                        np.asarray(route_c[1])[rows[i]]
                        != np.asarray(route[1])[rows[i]], axis=-1)
        del w
        if li == 0:
            xs[-1].block_until_ready()
            first_layer_s = time.perf_counter() - t0
    gaps, c_gaps = [], []
    for i, x in enumerate(xs):
        at = jnp.arange(len(rows[i]))
        lg = ref.logits(x[rows[i]], top, config)
        best = jnp.max(lg, axis=-1)
        gaps.append(np.asarray(best - lg[at, served[i]]))
        if control:
            lc = ref.logits(xc[i][rows[i]], top, config, control)
            c_gaps.append(np.asarray(best - lg[at, jnp.argmax(lc, axis=-1)]))
    gaps, margins = np.concatenate(gaps), np.concatenate(margins)
    decided = margins >= margin_min
    out = {"program": _max_mean(gaps[decided]),
           "undecided_share": float(np.mean(~decided)),
           "first_layer_s": first_layer_s,
           "positions": int(gaps.size), "requests": len(samples),
           "decided_positions": int(np.sum(decided)),
           "tokens_off_reference_best": int(np.sum(gaps > 0)),
           "longest": int(max(len(p) + len(o) for p, o in samples))}
    if control:
        cg, flipped = np.concatenate(c_gaps), np.concatenate(flipped)
        out["control"] = _max_mean(cg[decided])
        out["control_tokens_off_reference_best"] = int(np.sum(cg > 0))
        # the lower precision's routing against the reference's: where it
        # chose other experts, and how wide a margin that overcame
        out["control_flipped_positions"] = int(np.sum(flipped))
        out["control_flipped_and_decided"] = int(np.sum(flipped & decided))
        out["control_flipped_margin_max"] = (
            float(margins[flipped].max()) if flipped.any() else None)
        # for choosing margin_min: both sides' readings at several
        out["by_margin_min"] = {
            str(t): [*_max_mean(gaps[margins >= t]).values(),
                     *_max_mean(cg[margins >= t]).values(),
                     int(np.sum(margins < t))]
            for t in (0.0, 0.02, 0.05, 0.1, 0.2)}
    return out


def judge(config: dict, seed: int, samples, limits: dict, unfinished: int):
    """The numbers compared, each beside its limit, and whatever else the
    reference read.  With ``IN_PROGRAMS_PLACE`` set, the control's readings
    are the ones compared."""
    if samples:
        read = served_gap(config, seed, samples,
                          float(limits.get("router_margin_min", 0.0)),
                          IN_PROGRAMS_PLACE)
    else:
        read = {"program": _max_mean(np.empty(0)), "positions": 0,
                "undecided_share": 0.0}
        read["control"] = read["program"]
    side = read.pop("control" if IN_PROGRAMS_PLACE else "program")
    if IN_PROGRAMS_PLACE:
        read["in_programs_place"] = IN_PROGRAMS_PLACE
    checks = {name: [side[name], float(limits[name])]
              for name in ("logit_gap_max", "logit_gap_mean")}
    if "undecided_share_max" in limits:
        checks["undecided_share"] = [read.pop("undecided_share"),
                                     float(limits["undecided_share_max"])]
    checks["unfinished_after_drain"] = [unfinished, 0]
    return checks, read
