"""Shared MoE gating + expert dispatch primitives (raw-jnp level).

The ONE top-k gate / dispatch implementation in the repo.  Callers:

- ``models/mixtral.py`` eager block — GShard capacity buffers with
  drops, plus the load-balancing aux term (computed by the caller so
  the side state never enters a serving trace);
- ``incubate/distributed/models/moe/gate.py`` — NaiveGate/GShardGate/
  SwitchGate all route through :func:`topk_gate` (no second
  softmax/top-k copy drifting out of sync);
- ``jit/serving_step.py`` — :func:`moe_ffn` (a bank that holds every
  expert of its router) and :func:`moe_ffn_held` (a share of them) are
  the dropless MoE FFNs inside the compiled serving steps.  Each has
  its own gate; on one chip both hand their assignments to the ONE
  expert product, :func:`sorted_expert_swiglu`: the assignments sorted
  by expert into one buffer of rows and a grouped product sized by the
  rows each expert really has (on the TPU the Pallas kernel
  ``ops/pallas_kernels.grouped_expert_matmul``, which streams each
  expert's weights once; elsewhere three ``jax.lax.ragged_dot``s).
  Under an ``ep`` mesh axis :func:`moe_ffn` still scatters into
  per-expert buffers (the ``all_to_all`` pair wants static per-expert
  slices: the reference's global_scatter/global_gather, emitted inside
  the ONE compiled launch); those buffers are the eager block's too.

Everything here is pure jnp -> safe both under ``apply_op`` eager
dispatch and inside jit/shard_map traced bodies.  No host transfers, no
shape branches on traced values, no PRNG.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import device as _device
from .pallas_kernels import (grouped_buffer_rows, grouped_expert_matmul,
                             grouped_row_starts, grouped_slot_tables,
                             grouped_tile_rows, grouped_widths_ok)

__all__ = [
    "topk_gate", "assignment_slots", "dispatch_to_buffers",
    "grouped_expert_swiglu", "combine_from_buffers",
    "sorted_expert_swiglu", "moe_ffn", "group_limited_topk",
    "moe_ffn_held",
]


def topk_gate(logits, k, renormalize=True):
    """Softmax + top-k routing from raw router logits ``[N, E]``.

    Returns ``(top_w f32 [N,k], top_i int32 [N,k], probs f32 [N,E])``.
    ``renormalize=True`` rescales the selected weights to sum to 1
    (Mixtral convention); Switch-style gates pass ``False``.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if renormalize:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_w, top_i.astype(jnp.int32), probs


def assignment_slots(top_i, num_experts):
    """Per-assignment capacity slot: running count per expert over the
    flattened ``[N*k]`` assignment order (GShard dense-dispatch
    position, one-hot cumsum — never an ``[N,k,E,C]`` one-hot).

    Returns ``(slot int32 [N,k], oh f32 [N,k,E])``; ``oh`` is handed
    back so aux-loss callers don't recompute the one-hot.
    """
    oh = jax.nn.one_hot(top_i, num_experts, dtype=jnp.float32)
    pos = jnp.cumsum(oh.reshape(-1, num_experts), axis=0).reshape(
        oh.shape) - 1.0
    slot = jnp.sum(pos * oh, axis=-1).astype(jnp.int32)
    return slot, oh


def dispatch_to_buffers(x, top_i, slot, keep, num_experts, capacity):
    """Scatter tokens into ``[E, C, D]`` expert buffers (f32 scatter-add,
    cast back to ``x.dtype``).  ``keep=None`` means dropless (every
    assignment has a slot); otherwise over-capacity rows scatter zeros.
    """
    n, k = top_i.shape
    vf = x.astype(jnp.float32)
    if keep is None:
        src = jnp.broadcast_to(vf[:, None, :], (n, k, vf.shape[1]))
    else:
        src = vf[:, None, :] * keep[..., None]
    src = src.reshape(n * k, -1)
    slot_c = jnp.clip(slot, 0, capacity - 1)
    zeros = jnp.zeros((num_experts, capacity, vf.shape[1]), jnp.float32)
    return zeros.at[top_i.reshape(-1),
                    slot_c.reshape(-1)].add(src).astype(x.dtype)


def grouped_expert_swiglu(disp, wg, wu, wd):
    """Batched expert SwiGLU: the whole bank in three MXU einsums.

    ``disp [E, C, D]``, ``wg/wu [E, D, M]``, ``wd [E, M, D]`` ->
    ``[E, C, D]``.  Row results are independent of buffer contents, so
    capacity-buffer padding never perturbs real tokens.
    """
    g = jnp.einsum("ecd,edm->ecm", disp, wg)
    u = jnp.einsum("ecd,edm->ecm", disp, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(disp.dtype) * u
    return jnp.einsum("ecm,emd->ecd", h, wd)


def combine_from_buffers(eo, top_i, slot, top_w, keep=None):
    """Gather each assignment's expert output and k-sum with routing
    weights.  Returns f32 ``[N, D]`` (caller casts).  ``keep`` masks
    dropped assignments (eager capacity path)."""
    n, k = top_i.shape
    capacity = eo.shape[1]
    slot_c = jnp.clip(slot, 0, capacity - 1)
    picked = eo[top_i.reshape(-1), slot_c.reshape(-1)].reshape(n, k, -1)
    w_eff = top_w.astype(jnp.float32)
    if keep is not None:
        w_eff = (top_w * keep).astype(jnp.float32)
    return jnp.sum(picked.astype(jnp.float32) * w_eff[..., None], axis=1)


def sorted_expert_swiglu(x, top_i, top_w, wg, wu, wd, first_held=0,
                         valid=None, use_pallas=None, interpret=False):
    """The experts' part of a dropless MoE FFN, after the gate: the
    assignments ``top_i [N, k]`` (indices into the ROUTER's experts)
    that land on the experts held here (``first_held .. first_held +
    El``, ``wg/wu [El, D, M]``, ``wd [El, M, D]``) are sorted by expert
    into one static buffer of rows and multiplied by a grouped product
    sized by the rows each expert really has, never a buffer an
    expert; then un-sorted and summed over the k with ``top_w [N, k]``
    (float32).  ``valid`` (bool ``[N]``, all true if ``None``) marks
    the rows that are tokens: a row of padding is given to no expert,
    its output is 0 and no load counts it.

    The grouped product.  On the TPU (``use_pallas=None`` asks
    ``core.device.on_tpu()``; ``interpret`` runs the same kernel on
    the CPU; widths that are whole 128-lane tiles) it is
    ``ops/pallas_kernels.grouped_expert_matmul``, twice:
    each expert's rows start on a boundary of the row tile
    (``grouped_tile_rows``, from ``N * k`` and ``El``; the buffer is
    ``grouped_buffer_rows``: at most ``tile - 1`` rows of padding an
    expert), gate
    and up are one launch with ``silu(g) * u`` in float32 before the
    cast, and every weight crosses HBM once a product.  Anywhere else
    it is three ``jax.lax.ragged_dot``s over a ``[N * k, D]`` buffer
    with ``g`` and ``u`` rounded to ``x``'s type between: the plain
    form the kernel is tested against.  A step that holds either is
    traced with x64 off (XLA:TPU's 64-bit rewriter stops at a
    ragged-dot, Mosaic at an i64 scalar).

    Jitted (the lowering, ``first_held`` and ``interpret`` static), so
    the layers of a step share one traced body: a warm start is made of
    tracing.

    Returns ``(out [N, D] in x's type, load int32 [El])``: the rows
    each held expert was given."""
    if use_pallas is None:
        use_pallas = _device.on_tpu()
    kernel = (use_pallas or interpret) and grouped_widths_ok(
        x.shape[1], wg.shape[-1])
    return _sorted_expert_swiglu(x, top_i, top_w, wg, wu, wd, valid,
                                 first_held=first_held, kernel=kernel,
                                 interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("first_held", "kernel", "interpret"))
def _sorted_expert_swiglu(x, top_i, top_w, wg, wu, wd, valid, *,
                          first_held, kernel, interpret):
    n, d = x.shape
    top_k = top_i.shape[1]
    e_held = wg.shape[0]
    with jax.named_scope("moe.sort"):
        local = top_i.reshape(-1) - first_held                # [N*k]
        held = (local >= 0) & (local < e_held)
        if valid is not None:
            held &= jnp.repeat(valid, top_k)
        key = jnp.where(held, local, e_held)    # not held: sorted last
        order = jnp.argsort(key, stable=True)
        load = jnp.sum(jax.nn.one_hot(key, e_held + 1, dtype=jnp.int32),
                       axis=0)[:e_held]
        if kernel:
            # row p of the sorted order is row p + shift[its expert] of
            # the buffer whose experts each start on a tile boundary
            tile = grouped_tile_rows(n * top_k, e_held)
            rows = grouped_buffer_rows(n * top_k, e_held, tile)
            tiles, starts = grouped_row_starts(load, tile)
            ends = starts + tiles * tile        # one past an expert's tiles
            shift = starts - (jnp.cumsum(load) - load)
            r = jnp.arange(rows, dtype=jnp.int32)
            owner = jnp.minimum(jnp.sum(r[:, None] >= ends[None, :],
                                        axis=1), e_held - 1)
            src = jnp.clip(r - shift[owner], 0, n * top_k - 1)
            xs = x[order[src] // top_k]                       # [rows, D]
        else:
            xs = x[order // top_k]                            # [N*k, D]
    with jax.named_scope("moe.experts"):
        if kernel:
            slots = grouped_slot_tables(load, tile)
            h = grouped_expert_matmul(xs, slots, wg, wu, tile=tile,
                                      interpret=interpret)
            ys = grouped_expert_matmul(h, slots, wd, tile=tile,
                                       interpret=interpret)
        else:
            g = jax.lax.ragged_dot(xs, wg, load)
            u = jax.lax.ragged_dot(xs, wu, load)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
            ys = jax.lax.ragged_dot(h, wd, load)              # [N*k, D]
    with jax.named_scope("moe.combine"):
        # back to assignment order; a row no held expert computed is
        # whatever the grouped product left there: masked, not weighted
        back = jnp.argsort(order)
        if kernel:
            back = back + jnp.concatenate(
                [shift, jnp.zeros((1,), shift.dtype)])[key]
        y = jnp.where(held[:, None], ys[back].astype(jnp.float32), 0.0)
        out = jnp.sum(y.reshape(n, top_k, d)
                      * top_w[..., None], axis=1).astype(x.dtype)
    return out, load


def moe_ffn(x, gate_w, wg, wu, wd, *, top_k, ep_axis=None, ep_degree=1,
            valid=None, use_pallas=None):
    """Dropless fused MoE FFN over a flat token block ``x [N, D]``.

    ``gate_w [D, E_total]`` replicated; ``wg/wu/wd`` the LOCAL expert
    shard ``[El, ., .]`` (``El = E_total/ep``; the full bank when
    ``ep_degree == 1``).

    One chip (``ep_degree <= 1``): the shared top-k gate
    (:func:`topk_gate`, renormalised over the k), then
    :func:`sorted_expert_swiglu` over the whole bank: every assignment
    is a row of the one sorted ``[N*top_k, D]`` buffer, so none is
    dropped and no expert multiplies a row it was not given.  ``valid``
    (bool ``[N]``) marks the rows that are tokens; a pack's padding is
    given to no expert.  ``use_pallas`` chooses the grouped product's
    lowering there (``None``: the Pallas kernel on a TPU).

    ep path (inside shard_map over ``ep_axis``): chip ``r`` gates its
    token stripe ``x[r*Tl:(r+1)*Tl]``, scatters into a per-expert send
    buffer ``[E_total, Tl*k, D]``, ``all_to_all`` ships each expert
    owner its slices, grouped SwiGLU runs on the local ``[El, ., .]``
    shard, ``all_to_all`` ships outputs back, the weighted combine runs
    on the token's home chip, and ``all_gather`` rebuilds the
    replicated ``[N, D]`` activation.  Requires ``ep | N`` and
    ``ep | E_total`` (validated at engine construction).  The send
    buffers are dropless by capacity (``Tl*k`` bounds any expert's
    load), so the exchange ships E times the rows that carry a token;
    ``valid`` is not looked at (padding is computed and discarded).

    Returns ``(out [N, D] in x's type, load)``: ``load int32 [E]`` the
    rows each expert was given on one chip, ``None`` under ep (the
    count is not made there).
    """
    n, d = x.shape
    e_local = wg.shape[0]
    if gate_w.shape[-1] != e_local * max(1, ep_degree):
        raise ValueError(
            "moe_ffn: the router is %d experts wide and the bank holds "
            "%d (x ep %d): a bank narrower than its router has to be "
            "told which experts it holds — moe_ffn_held(first_held=...)"
            % (gate_w.shape[-1], e_local, max(1, ep_degree)))
    if ep_axis is None or ep_degree <= 1:
        with jax.named_scope("moe.gate"):
            logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
            top_w, top_i, _ = topk_gate(logits, top_k)
        return sorted_expert_swiglu(x, top_i, top_w, wg, wu, wd,
                                    valid=valid, use_pallas=use_pallas)

    e_total = e_local * ep_degree
    tl = n // ep_degree                 # token stripe per chip
    cl = tl * top_k                     # dropless send capacity
    with jax.named_scope("moe.gate"):
        r = jax.lax.axis_index(ep_axis)
        x_r = jax.lax.dynamic_slice_in_dim(x, r * tl, tl, axis=0)
        logits = x_r.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        top_w, top_i, _ = topk_gate(logits, top_k)
        slot, _ = assignment_slots(top_i, e_total)
    with jax.named_scope("moe.dispatch"):
        disp = dispatch_to_buffers(x_r, top_i, slot, None, e_total, cl)
        # dispatch: chip g receives [ep, El, Cl, D]; recv[r] = chip r's
        # assignments destined to chip g's experts
        with jax.named_scope("ep.all_to_all"):
            recv = jax.lax.all_to_all(disp, ep_axis, split_axis=0,
                                      concat_axis=0, tiled=True)
        work = jnp.swapaxes(recv.reshape(ep_degree, e_local, cl, d),
                            0, 1).reshape(e_local, ep_degree * cl, d)
    with jax.named_scope("moe.experts"):
        eo = grouped_expert_swiglu(work, wg, wu, wd)
    with jax.named_scope("moe.combine"):
        back = jnp.swapaxes(eo.reshape(e_local, ep_degree, cl, d),
                            0, 1).reshape(e_total, cl, d)
        # combine: ship outputs back to each assignment's home chip;
        # after the exchange chip r holds [E_total, Cl, D] aligned with
        # its own (top_i, slot) tables
        with jax.named_scope("ep.all_to_all"):
            back = jax.lax.all_to_all(back, ep_axis, split_axis=0,
                                      concat_axis=0, tiled=True)
        out_r = combine_from_buffers(back, top_i, slot,
                                     top_w).astype(x.dtype)
        return jax.lax.all_gather(out_r, ep_axis, axis=0,
                                  tiled=True), None


def group_limited_topk(scores, k, n_group, topk_group):
    """Group-limited greedy top-k over ``scores [N, E]`` (any float;
    the caller's softmax): the experts lie in ``n_group`` groups of
    ``E / n_group`` consecutive ones, a group scores as its best
    expert, the ``topk_group`` best groups are kept, and the ``k`` best
    experts among the kept groups are chosen.  Returns ``(top_s [N, k]``
    the chosen experts' own scores, not renormalised, ``top_i int32
    [N, k])``."""
    n, e = scores.shape
    if e % n_group:
        raise ValueError("group_limited_topk: %d experts do not divide "
                         "into %d groups" % (e, n_group))
    per = e // n_group
    group_s = jnp.max(scores.reshape(n, n_group, per), axis=-1)
    _, group_i = jax.lax.top_k(group_s, topk_group)           # [N, g]
    kept = jnp.any(group_i[..., None] == jnp.arange(n_group), axis=1)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), scores, -jnp.inf)
    top_s, top_i = jax.lax.top_k(masked, k)
    return top_s, top_i.astype(jnp.int32)


def moe_ffn_held(x, gate_w, wg, wu, wd, *, top_k, first_held=0,
                 n_group=1, topk_group=1, routed_scale=1.0, valid=None,
                 use_pallas=None):
    """Dropless routed-expert FFN over ``x [N, D]`` for a bank that
    holds a SHARE of the experts its router scores: ``gate_w [D, E]``
    is the router at its full width, ``wg/wu [El, D, M]`` and ``wd [El,
    M, D]`` the experts ``first_held .. first_held + El`` held here.

    The router (softmax in float32, group-limited top-k, the weights
    ``score * routed_scale``, never renormalised over the k) is computed
    over all E; the assignments that land on held experts go through
    :func:`sorted_expert_swiglu`.  What the experts held elsewhere
    would add is left out: the result is this bank's part of the sum.
    ``valid`` (bool ``[N]``, all true if ``None``) marks the rows that
    are tokens: a row of padding is given to no expert, its output is
    0 and no load counts it.  ``use_pallas`` chooses the grouped
    product's lowering (``None``: the Pallas kernel on a TPU).

    Returns ``(out [N, D] in x's type, load int32 [El])``: the rows
    each held expert was given."""
    e_held = wg.shape[0]
    e_all = gate_w.shape[-1]
    if not 0 <= first_held <= e_all - e_held:
        raise ValueError(
            "moe_ffn_held: experts %d..%d held of a router %d wide"
            % (first_held, first_held + e_held, e_all))
    with jax.named_scope("moe.gate"):
        logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = group_limited_topk(probs, top_k, n_group,
                                          topk_group)
        top_w = top_w * jnp.float32(routed_scale)
    return sorted_expert_swiglu(x, top_i, top_w, wg, wu, wd,
                                first_held=first_held, valid=valid,
                                use_pallas=use_pallas)
