"""Weights from ``--seed``, made on the device, a group of leaves per
jitted call, in the type they are served in.

Both sides call this: ``program.py`` to fill the system's model, and the
references to get the very same values (they take nothing the program
made).  A group is one decoder layer, or the top (embedding, final norm,
head).  Matrices are uniform with standard deviation 0.02; norm gains are 1 plus
uniform noise of standard deviation 0.1, so a gain that is dropped shows.

A leaf that is neither a matrix nor a gain (a decay's ``A_log``, a time
step's ``dt_bias``) is drawn as its family's reference names it
(``leaf_draws``, ``spec.family_layers``): a draw is a tuple of this
module's vocabulary, ``(kind, lo, hi)`` with an optional last element,
the transform:

``("uniform", lo, hi)``
    ``lo + (hi - lo) u``: FLA's ``GatedDeltaNet`` draws ``A`` so from
    (0, 16), Mamba2 from ``A_init_range`` (1, 16).
``("log_uniform", lo, hi)``, ``0 < lo``
    ``exp(log lo + (log hi - log lo) u)``: Mamba2's and ``GatedDeltaNet``'s
    time step ``dt``, from ``dt_min`` 0.001 to ``dt_max`` 0.1.
transform ``"log"``, ``0 <= lo``
    the log of the value: ``A_log = log A``.
transform ``"softplus_inv"``, ``0 <= lo``
    ``v + log(-expm1(-v))``, the inverse of softplus: ``dt_bias``, so that
    ``softplus(dt_bias)`` is the drawn ``dt``.

``u`` is the very uniform that the leaf's unnamed rule would use (the
same key, the same index in the group), taken to [0, 1) and held off
both ends by 2**-24, so a log or an inverse never meets 0.  A leaf no
draw names keeps the rule above, bit for bit.
"""
from __future__ import annotations

import functools
import math
import numbers

import jax
import jax.numpy as jnp

TOP = -1            # group index of embedding / final norm / head
STD = 0.02
SQRT3 = 3.0 ** 0.5
DRAWS = ("uniform", "log_uniform")
TRANSFORMS = ("log", "softplus_inv")
_EDGE = 2.0 ** -24


def _key(seed: int, group: int):
    # --seed may pass 2**31: split it so each half fits an int32
    seed = int(seed)
    k = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    k = jax.random.fold_in(k, seed >> 31)
    return jax.random.fold_in(k, group + 1)


def check_draws(draws: dict, shapes: dict, owner: str) -> None:
    """Refuses a draw outside the vocabulary, or one for a leaf that
    ``shapes`` does not have; ``owner`` names who asked for it."""
    for leaf, draw in draws.items():
        where = f"{owner}: leaf {leaf!r}, draw {draw!r}"
        if leaf not in shapes:
            raise ValueError(f"{where}: no such leaf; the leaves are "
                             f"{sorted(shapes)}")
        if (not isinstance(draw, tuple) or len(draw) not in (3, 4)
                or draw[0] not in DRAWS
                or not all(isinstance(b, numbers.Real)
                           and math.isfinite(b) for b in draw[1:3])
                or (len(draw) == 4 and draw[3] not in TRANSFORMS)):
            raise ValueError(f"{where}: a draw is (kind, lo, hi) or (kind, "
                             f"lo, hi, transform), kind one of {DRAWS}, "
                             f"transform one of {TRANSFORMS}")
        lo, hi = draw[1:3]
        if (not lo < hi or (draw[0] == "log_uniform" and lo <= 0)
                or (len(draw) == 4 and lo < 0)):
            raise ValueError(f"{where}: wants lo < hi, 0 < lo for "
                             f"log_uniform and 0 <= lo under a transform")


def _drawn(x, draw):
    """The value of ``draw`` from ``x``, uniform on (-SQRT3, SQRT3)."""
    kind, lo, hi = draw[:3]
    u = jnp.clip((x + SQRT3) / (2.0 * SQRT3), _EDGE, 1.0 - _EDGE)
    if kind == "uniform":
        v = lo + (hi - lo) * u
    else:
        v = jnp.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * u)
    if draw[3:] == ("log",):
        v = jnp.log(v)
    elif draw[3:] == ("softplus_inv",):
        v = v + jnp.log(-jnp.expm1(-v))
    return v


@functools.partial(jax.jit, static_argnames=("shapes", "dtype", "draws"))
def _make(key, shapes, dtype, draws):
    draws = dict(draws)
    out = {}
    for i, (name, shape) in enumerate(shapes):
        # uniform with the wanted standard deviation: an erf_inv per
        # element made a 7.5 GB model take 11 s (my chip run, PR 23)
        x = jax.random.uniform(jax.random.fold_in(key, i), shape,
                               jnp.float32, -SQRT3, SQRT3)
        if name in draws:
            x = _drawn(x, draws[name])
        else:
            x = 1.0 + 0.1 * x if len(shape) == 1 else STD * x
        out[name] = x.astype(dtype)
    return out


def make_group(seed: int, group: int, shapes: dict, dtype,
               draws: dict | None = None) -> dict:
    """``{leaf name: array}`` for one group; ``shapes`` maps names to
    shapes and ``draws`` names the draw of some leaves (the reference
    module of the family gives both; ``spec.family_layers`` has checked
    the draws)."""
    frozen = tuple((k, tuple(int(d) for d in v)) for k, v in shapes.items())
    return _make(_key(seed, group), frozen, jnp.dtype(dtype),
                 tuple(sorted((draws or {}).items())))
