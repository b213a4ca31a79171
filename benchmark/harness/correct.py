"""Decides ``correct`` for a serving cell.

Once the window has closed and the program's state is freed, a sample of
the requests it finished (drawn from the seed, the longest always in it,
no document of a cycled pool twice) goes through the plain reference, one
forward over each prompt with its served tokens.  What is read is the gap
by which a served token's reference logit lies below the reference's best
at that position: 0 where the program chose what the reference chooses.
The numbers compared are those the cell's ``correct`` gives a limit for:

``logit_gap_max``, ``logit_gap_mean``
    the widest gap and the mean of the gaps;
``wide_gap_share``, ``capped_gap_mean`` (with ``wide_gap``)
    the share of the gaps that are wider than ``wide_gap``, and the mean
    with each gap counted up to ``wide_gap`` and no further.  For a routed
    model: a sound program now and then gives a token other experts than
    the reference does and then serves another token, one wide gap that
    says nothing of the rest; a fault or a lower precision widens many.

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
from .spec import family_layers

PAD_TO = 1024       # sequences are padded to a multiple: few programs
IN_PROGRAMS_PLACE = None    # None | "int8" (the control) | "bf16" (a witness)


def pick_sample(tracks, seed: int, n: int):
    """``n`` finished requests: the longest, and others drawn from the
    seed.  A closed loop cycles its pool of documents: of those that
    finished more than once, the first."""
    first = {}
    for t in tracks:
        if (t.measured and t.done and not t.handle.truncated
                and len(t.handle.output_ids)):
            first.setdefault(t.req.index, t)
    if not first:
        return []
    done = [first[i] for i in sorted(first)]
    longest = max(done, key=lambda t: len(t.req.prompt) + t.req.n_out)
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([int(seed), 3])
    take = min(n - 1, len(rest))
    picked = [rest[i] for i in rng.choice(len(rest), take, replace=False)]
    return [longest] + picked


def _numbers(gaps, wide_gap=None):
    """The numbers a cell may compare (the module's docstring)."""
    if not gaps.size:
        gaps = np.full(1, np.inf)
    out = {"logit_gap_max": float(gaps.max()),
           "logit_gap_mean": float(gaps.mean())}
    if wide_gap is not None:
        out["wide_gap_share"] = float(np.mean(gaps > wide_gap))
        out["capped_gap_mean"] = float(np.mean(np.minimum(gaps, wide_gap)))
    return out


def served_gap(cell, seed: int, samples, margin_min: float = 0.0,
               control=None, wide_gap=None):
    """``samples``: [(prompt ids, served ids)].  Returns the program's
    readings (``"program"``), what they were taken over and, with
    ``control`` (a precision of the reference's ``mm``), that forward's
    readings at the same positions (``"control"``).

    A sparse model's router decides some tokens' experts by less than
    rounding.  The program, in the precision the configuration states, may
    decide those otherwise, and then serves a token the reference does
    not, however sound it is.  So the gaps are read over the DECIDED
    positions: those whose router margin (the reference's own) is at least
    ``margin_min`` in every layer that routes.  The share left out is
    reported and held to a cap.

    Layer ``li`` gets the leaves of its kind (``spec.family_layers``), and
    a family with kinds has its ``layer`` handed the kind."""
    import jax
    import jax.numpy as jnp

    config, ref = cell.config, cell.reference()
    dtype = config["dtype"]
    kinds, shapes = family_layers(ref, config)

    def layer_of(kind, prec):       # one program a kind and a precision
        kw = {} if kind is None else {"kind": kind}
        return jax.jit(lambda x, w: ref.layer(x, w, config, prec, **kw))

    layers = {k: layer_of(k, None) for k in shapes}
    layers_c = {k: layer_of(k, control) for k in shapes} if control else {}
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
    t0 = time.perf_counter()
    for li, kind in enumerate(kinds):
        w = weights.make_group(seed, li, shapes[kind], dtype)
        for i, x in enumerate(xs):
            xs[i], route = layers[kind](x, w)
            if route is not None:
                margins[i] = np.minimum(margins[i],
                                        np.asarray(route[0])[rows[i]])
            if control:
                xc[i], route_c = layers_c[kind](xc[i], w)
                if route is not None:
                    flipped[i] |= np.any(
                        np.asarray(route_c[1])[rows[i]]
                        != np.asarray(route[1])[rows[i]], axis=-1)
        del w
        if li == 0:
            xs[-1].block_until_ready()
            first_layer_s = time.perf_counter() - t0
    # one program for the head, whatever a request's length: each takes
    # as many rows as the longest (its last repeated); op by op every new
    # length compiled anew, half of the reference's seconds (PR 26)
    n_rows = max(len(r) for r in rows)

    def padded(a):
        return jnp.asarray(np.pad(a, (0, n_rows - len(a)), mode="edge"))

    @jax.jit
    def gap_of(x, at, token, top):  # the reference's best minus token's
        lg = ref.logits(x[at], top, config)
        return jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, token[:, None], axis=-1)[:, 0]

    first_of = jax.jit(lambda x, at, top: jnp.argmax(
        ref.logits(x[at], top, config, control), axis=-1))
    gaps, c_gaps = [], []
    t_head = time.perf_counter()
    for i, x in enumerate(xs):
        at, n = padded(rows[i]), len(rows[i])
        gaps.append(np.asarray(gap_of(x, at, padded(served[i]), top))[:n])
        if control:     # the gap of the token the control puts first
            c_gaps.append(np.asarray(
                gap_of(x, at, first_of(xc[i], at, top), top))[:n])
    t_gaps = time.perf_counter()
    gaps, margins = np.concatenate(gaps), np.concatenate(margins)
    decided = margins >= margin_min
    where = np.concatenate([np.stack([np.full(len(r), i), r], axis=1)
                            for i, r in enumerate(rows)])
    widest = np.argsort(-np.where(decided, gaps, -1.0))[:5]
    out = {"program": _numbers(gaps[decided], wide_gap),
           "undecided_share": float(np.mean(~decided)),
           "first_layer_s": first_layer_s,
           "head_s": t_gaps - t_head,
           "positions": int(gaps.size), "requests": len(samples),
           "decided_positions": int(np.sum(decided)),
           "tokens_off_reference_best": int(np.sum(gaps > 0)),
           "longest": int(max(len(p) + len(o) for p, o in samples)),
           # the widest decided gaps: [sample, position, gap, margin]
           "widest": [[int(where[j, 0]), int(where[j, 1]), float(gaps[j]),
                       float(margins[j])] for j in widest if decided[j]]}
    if control:
        cg, flipped = np.concatenate(c_gaps), np.concatenate(flipped)
        out["control"] = _numbers(cg[decided], wide_gap)
        out["control_tokens_off_reference_best"] = int(np.sum(cg > 0))
        # the lower precision's routing against the reference's: where it
        # chose other experts, and how wide a margin that overcame
        out["control_flipped_positions"] = int(np.sum(flipped))
        out["control_flipped_and_decided"] = int(np.sum(flipped & decided))
        out["control_flipped_margin_max"] = (
            float(margins[flipped].max()) if flipped.any() else None)
        # for choosing margin_min: both sides' readings at several
        out["by_margin_min"] = {
            str(t): [*_numbers(gaps[margins >= t], wide_gap).values(),
                     *_numbers(cg[margins >= t], wide_gap).values(),
                     int(np.sum(margins < t))]
            for t in (0.0, 0.02, 0.05, 0.1, 0.2)}
    return out


def judge(cell, seed: int, samples, unfinished: int):
    """The numbers compared, each beside its limit (the cell's
    ``correct``), and whatever else the reference read.  With
    ``IN_PROGRAMS_PLACE`` set, the control's readings are the ones
    compared."""
    limits = cell.deploy["correct"]
    wide_gap = limits.get("wide_gap")
    if samples:
        read = served_gap(cell, seed, samples,
                          float(limits.get("router_margin_min", 0.0)),
                          IN_PROGRAMS_PLACE, wide_gap)
    else:
        read = {"program": _numbers(np.empty(0), wide_gap), "positions": 0,
                "undecided_share": 0.0}
        read["control"] = read["program"]
    side = read.pop("control" if IN_PROGRAMS_PLACE else "program")
    if IN_PROGRAMS_PLACE:
        read["in_programs_place"] = IN_PROGRAMS_PLACE
    checks = {name: [value, float(limits[name])]
              for name, value in side.items() if name in limits}
    if not checks:
        raise ValueError(f"{cell.name}: its 'correct' limits no gap")
    read["gaps"] = side             # those not compared too
    if "undecided_share_max" in limits:
        checks["undecided_share"] = [read.pop("undecided_share"),
                                     float(limits["undecided_share_max"])]
    checks["unfinished_after_drain"] = [unfinished, 0]
    return checks, read
