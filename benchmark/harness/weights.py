"""Weights from ``--seed``, made on the device, a group of leaves per
jitted call, in the type they are served in.

Both sides call this: ``program.py`` to fill the system's model, and the
references to get the very same values (they take nothing the program
made).  A group is one decoder layer, or the top (embedding, final norm,
head).  Matrices are uniform with standard deviation 0.02; norm gains are 1 plus
uniform noise of standard deviation 0.1, so a gain that is dropped shows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TOP = -1            # group index of embedding / final norm / head
STD = 0.02
SQRT3 = 3.0 ** 0.5


def _key(seed: int, group: int):
    # --seed may pass 2**31: split it so each half fits an int32
    seed = int(seed)
    k = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    k = jax.random.fold_in(k, seed >> 31)
    return jax.random.fold_in(k, group + 1)


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, shapes, dtype):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        # uniform with the wanted standard deviation: an erf_inv per
        # element made a 7.5 GB model take 11 s (my chip run, PR 23)
        x = jax.random.uniform(jax.random.fold_in(key, i), shape,
                               jnp.float32, -SQRT3, SQRT3)
        x = 1.0 + 0.1 * x if len(shape) == 1 else STD * x
        out[name] = x.astype(dtype)
    return out


def make_group(seed: int, group: int, shapes: dict, dtype) -> dict:
    """``{leaf name: array}`` for one group; ``shapes`` maps names to
    shapes (the reference module of the family gives them)."""
    frozen = tuple((k, tuple(int(d) for d in v)) for k, v in shapes.items())
    return _make(_key(seed, group), frozen, jnp.dtype(dtype))
