"""Microbatch pipeline parallelism over the ``pipe`` mesh axis.

The reference's closest machinery is update-during-backward overlap
(``TrainerInternal.cpp:70`` ``doPipelineUpdate``) — true pipeline
parallelism did not exist in 2017; this is a forward-looking "exceeds" item
completing the parallelism matrix (dp / tp / sp / ep / **pp**).

Scheme: GPipe-style. Each device owns one stage's parameters; microbatches
enter at stage 0, activations hop stage-to-stage with ``lax.ppermute``
(neighbor ICI transfers), and the last stage collects outputs. The schedule
is the classic ``M + S - 1`` step wavefront with bubbles at the ends;
everything is differentiable (``ppermute`` has a transpose rule), so
``jax.grad`` through the pipeline just works — the backward pass is the
reverse wavefront XLA derives automatically.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "make_pipeline", "pipeline_loss_apply",
           "make_pipeline_loss", "pipeline_grads_1f1b",
           "make_pipeline_1f1b"]


def _wavefront(stage_fn: Callable, stage_params, x, axis_name: str,
               axis_size: int, comm_dtype=None):
    """The shared GPipe M + S - 1-tick wavefront — call INSIDE shard_map.
    Runs microbatches through the stage ring (ppermute hops) and returns
    the last stage's [M, ...] output buffer (meaningful ONLY on stage
    S-1; other devices hold zeros/garbage). ``comm_dtype`` (e.g. bf16)
    compresses the hop wire; local compute and the output buffer keep the
    stage dtype."""
    S = axis_size
    stage = lax.axis_index(axis_name)
    M = x.shape[0]
    if comm_dtype is not None:
        x = x.astype(comm_dtype)

    def body(t, carry):
        act, outbuf = carry
        # stage 0 injects microbatch t (zeros past the end — bubble)
        inject = jnp.where(t < M, x[jnp.clip(t, 0, M - 1)],
                           jnp.zeros_like(x[0]))
        act_in = jnp.where(stage == 0, inject, act)
        y = stage_fn(stage_params, act_in)
        # hop to the next stage around the ring; optimization_barrier
        # pins the downcast to the send side (XLA otherwise reorders
        # convert across the collective and cancels it)
        send = y if comm_dtype is None \
            else lax.optimization_barrier(y.astype(comm_dtype))
        act_next = lax.ppermute(send, axis_name,
                                [(i, (i + 1) % S) for i in range(S)])
        # the last stage finishes microbatch m = t - (S - 1)
        m = t - (S - 1)
        write = (stage == S - 1) & (m >= 0) & (m < M)
        outbuf = jnp.where(write,
                           outbuf.at[jnp.clip(m, 0, M - 1)].set(y),
                           outbuf)
        return act_next, outbuf

    # Derive the buffers from the (device-varying) probe output so they
    # carry the pipe axis in their varying-axes set — plain zeros constants
    # would trip shard_map's carry check (same trick as ring.py's
    # accumulators).
    y0 = stage_fn(stage_params, x[0])      # shape probe for buffers
    act0 = y0 * 0.0 if comm_dtype is None else (y0 * 0.0).astype(comm_dtype)
    outbuf0 = jnp.broadcast_to((y0 * 0.0)[None], (M,) + y0.shape)
    _, outbuf = lax.fori_loop(0, M + S - 1, body, (act0, outbuf0))
    return outbuf


def pipeline_apply(stage_fn: Callable, stage_params, x, axis_name: str,
                   axis_size: int):
    """Run the S-stage pipeline — call INSIDE shard_map.

    ``stage_params``: THIS device's stage parameters (the [S, ...] stack
    sharded over ``axis_name``, leading axis squeezed). ``x``: the full
    microbatch stack [M, mb, ...], replicated (only stage 0 reads it).
    Returns the final outputs [M, mb, ...] (replicated via a psum
    broadcast from the last stage — a full-tensor sync; for TRAINING use
    :func:`pipeline_loss_apply`, which closes the loss on the last stage
    and syncs only a scalar).
    """
    outbuf = _wavefront(stage_fn, stage_params, x, axis_name, axis_size)
    stage = lax.axis_index(axis_name)
    # broadcast the last stage's buffer to every device. jnp.where, not a
    # multiplicative mask: non-finite values in a bubble device's buffer
    # would poison the psum through NaN * 0 == NaN.
    sel = jnp.where(stage == axis_size - 1, outbuf, jnp.zeros_like(outbuf))
    return lax.psum(sel, axis_name)


def make_pipeline(mesh: Mesh, stage_fn: Callable, pipe_axis: str = "pipe"):
    """Wrap :func:`pipeline_apply` in shard_map over ``mesh``.

    Takes GLOBAL arrays: ``stage_params`` with a leading [S, ...] stage axis
    (sharded over ``pipe_axis``) and microbatches ``x [M, mb, ...]``
    (replicated). ``stage_fn(params_one_stage, act)`` must keep the
    activation shape (homogeneous pipeline; the usual transformer-stack
    case)."""
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[pipe_axis]

    def inner(stage_params, x):
        def squeeze(a):
            assert a.shape[0] == 1, (
                f"stage stack must have exactly {S} stages (the pipe-axis "
                f"size); got a shard of {a.shape[0]} stages per device")
            return a[0]
        squeezed = jax.tree_util.tree_map(squeeze, stage_params)
        return pipeline_apply(stage_fn, squeezed, x, pipe_axis, S)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(pipe_axis), P()),
        out_specs=P())


def pipeline_loss_apply(stage_fn: Callable, stage_params, x,
                        final_fn: Callable, final_params, extras,
                        axis_name: str, axis_size: int, reduce_axes=(),
                        comm_dtype=None):
    """GPipe wavefront + ON-LAST-STAGE loss — call INSIDE shard_map.

    Same wavefront as :func:`pipeline_apply`, but instead of broadcasting
    the completed [M, mb, ...] output stack to every stage (a full-tensor
    ``psum`` over the pipe axis — 1.07 GB/step of wire for a d1024 LM,
    counted in the compiled HLO, r5), the loss closes on
    the last stage: ``final_fn(final_params, outbuf, *extras)`` maps the
    stack to a scalar, non-last stages contribute zero, and only the
    SCALAR crosses the wire. Every device traces ``final_fn`` (bubble
    devices run it on zeros — wasted FLOPs that overlap the bubble, no
    wire); grads for ``stage_params``, ``final_params``, and ``x`` all
    flow (the masked psum routes the cotangent to the last stage)."""
    outbuf = _wavefront(stage_fn, stage_params, x, axis_name, axis_size,
                        comm_dtype=comm_dtype)
    stage = lax.axis_index(axis_name)
    S = axis_size
    # Double-where, not val * mask: bubble devices would run final_fn on a
    # zero buffer, and a non-finite val there (0/0 counts, log 0, ...)
    # poisons the psum through NaN * 0 == NaN — and even with an outer
    # where, the BACKWARD multiplies the zeroed cotangent into final_fn's
    # inf/NaN partials (0 * inf == NaN again). So bubble devices evaluate
    # final_fn on a safe all-ones buffer (finite value AND finite partials
    # for the 0/0-normalisation class), and the outer where discards it.
    is_last = stage == S - 1
    safe = jnp.where(is_last, outbuf, jnp.ones_like(outbuf))
    val = final_fn(final_params, safe, *extras)
    sel = jnp.where(is_last, val, jnp.zeros_like(val))
    # reduce_axes: batch-sharding axes of x/extras (dp x pp) whose partial
    # losses must also sum into the global scalar
    return lax.psum(sel, (axis_name,) + tuple(reduce_axes))


def make_pipeline_loss(mesh: Mesh, stage_fn: Callable, final_fn: Callable,
                       pipe_axis: str = "pipe", x_spec: P = P(),
                       extra_specs=(), reduce_axes=(), comm_dtype=None):
    """Wrap :func:`pipeline_loss_apply` in shard_map over ``mesh``.

    Returns ``fn(stage_params, final_params, x, *extras) -> scalar``.
    ``stage_params``: [S, ...] stacks sharded over ``pipe_axis``;
    ``final_params``: replicated pytree consumed by the last-stage loss
    (head weights, tied embeddings — its grads psum over the mesh);
    ``x``: the [M, mb, ...] microbatch stack (``x_spec`` may shard mb over
    a data axis for dp x pp — name that axis in ``reduce_axes`` so the
    per-group partial losses sum into the global scalar); ``extras``:
    per-microbatch aux arrays (targets, masks) with specs
    ``extra_specs``."""
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[pipe_axis]

    def inner(stage_params, final_params, x, *extras):
        def squeeze(a):
            assert a.shape[0] == 1, (
                f"stage stack must have exactly {S} stages (the pipe-axis "
                f"size); got a shard of {a.shape[0]} stages per device")
            return a[0]
        squeezed = jax.tree_util.tree_map(squeeze, stage_params)
        return pipeline_loss_apply(stage_fn, squeezed, x, final_fn,
                                   final_params, extras, pipe_axis, S,
                                   reduce_axes=reduce_axes,
                                   comm_dtype=comm_dtype)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(pipe_axis), P(), x_spec) + tuple(extra_specs),
        out_specs=P())


def _call_loss(loss_fn, out, mb_index):
    """loss_fn may take (out) or (out, microbatch_index) — the index form
    lets per-microbatch targets live in closure arrays."""
    import inspect
    try:
        n = len(inspect.signature(loss_fn).parameters)
    except (TypeError, ValueError):
        n = 1
    return loss_fn(out, mb_index) if n >= 2 else loss_fn(out)


def pipeline_grads_1f1b(stage_fn: Callable, loss_fn: Callable, stage_params,
                        x, axis_name: str, axis_size: int):
    """One-forward-one-backward (1F1B) training schedule — call INSIDE
    shard_map. Returns ``(total_loss, param_grads)`` for
    ``sum_m loss_fn(pipeline(x[m]))``.

    Where GPipe (``pipeline_apply`` + ``jax.grad``) runs all M forwards then
    all M backwards and therefore holds M microbatches of saved activations
    per stage, 1F1B interleaves: on the standard half-step grid, stage ``s``
    runs forward #i at t = s + 2i and backward #i at t = 2S-1-s + 2i, so at
    most S microbatches are ever in flight and the stage-input buffer is a
    fixed ``[S, ...]`` ring regardless of M — the schedule that makes
    M >> S gradient accumulation memory-feasible. The backward recomputes
    the stage forward from its saved INPUT (`jax.vjp` at backward time):
    boundary-only saving + in-stage rematerialisation, the standard
    memory/FLOP trade.

    Compute cost: off-tick events are skipped via ``lax.cond`` (HLO
    conditional), so each device executes exactly M forwards and M
    recompute-vjp passes over the whole schedule — the ideal 1F1B budget
    plus the rematerialisation forward, NOT ``T = 2M+2S-2`` copies of each
    (the pre-round-4 version ran every event on every tick and masked the
    results, ~3x the FLOPs).
    """
    S = axis_size
    stage = lax.axis_index(axis_name)
    M = x.shape[0]
    T = 2 * M + 2 * S - 2                     # last event: t = 2M + 2S - 3
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    # Probes derive every buffer from device-varying values (shard_map
    # varying-axes rule, same trick as pipeline_apply / ring.py).
    y0 = stage_fn(stage_params, x[0])
    act0 = y0 * 0.0                               # inter-stage activation
    cot0 = y0 * 0.0                               # inter-stage cotangent
    abuf0 = jnp.broadcast_to((y0 * 0.0)[None], (S,) + y0.shape)
    grad0 = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
    loss0 = jnp.sum(y0) * 0.0

    def body(t, carry):
        fwd_act, bwd_cot, abuf, gacc, lacc = carry

        # Off-tick events SKIP their stage compute via lax.cond (HLO
        # conditional executes one branch): per tick a device runs at most
        # one forward and one recompute-vjp, so total stage executions are
        # M fwd + M recompute + M bwd per device — the ideal 1F1B compute
        # budget — not T = 2M+2S-2 of each. The predicates are
        # device-varying (stage enters them), which shard_map's varying-axes
        # tracking allows for collective-free branches; the ppermute hops
        # stay outside, executed by every device every tick.

        # -- forward event: t == stage + 2*fi -------------------------------
        df = t - stage
        fi = df // 2
        fwd_on = (df >= 0) & (df % 2 == 0) & (fi < M)
        f_in = jnp.where(stage == 0, x[jnp.clip(fi, 0, M - 1)], fwd_act)

        def do_fwd(abuf):
            return stage_fn(stage_params, f_in), abuf.at[fi % S].set(f_in)

        send_f, abuf = lax.cond(fwd_on, do_fwd,
                                lambda abuf: (act0, abuf), abuf)

        # -- backward event: t == 2S-1-stage + 2*bi -------------------------
        db = t - (2 * S - 1 - stage)
        bi = db // 2
        bwd_on = (db >= 0) & (db % 2 == 0) & (bi < M)
        b_in = abuf[jnp.clip(bi, 0, M - 1) % S]

        def fwd_loss(p, a):
            out = stage_fn(p, a)
            # last stage closes the loss; others forward the cotangent.
            # loss_fn takes (out_mb, mb_index) so per-microbatch targets
            # (labels, masks) can be indexed from closure state.
            l = _call_loss(loss_fn, out, jnp.clip(bi, 0, M - 1))
            return jnp.where(stage == S - 1, l, jnp.sum(out * bwd_cot)), l

        def do_bwd(_):
            val, vjp, l = jax.vjp(fwd_loss, stage_params, b_in, has_aux=True)
            dparams, dact = vjp(jnp.ones_like(val))
            return dparams, dact, l

        dparams, send_b, l = lax.cond(
            bwd_on, do_bwd, lambda _: (grad0, cot0, loss0), None)
        gacc = jax.tree_util.tree_map(lambda g, d: g + d, gacc, dparams)
        lacc = lacc + jnp.where(stage == S - 1, l, 0.0)

        # -- hops ------------------------------------------------------------
        fwd_act_next = lax.ppermute(send_f, axis_name, fwd_perm)
        bwd_cot_next = lax.ppermute(send_b, axis_name, bwd_perm)
        return fwd_act_next, bwd_cot_next, abuf, gacc, lacc

    _, _, _, grads, loss = lax.fori_loop(
        0, T, body, (act0, cot0, abuf0, grad0, loss0))
    return lax.psum(loss, axis_name), grads


def make_pipeline_1f1b(mesh: Mesh, stage_fn: Callable, loss_fn: Callable,
                       pipe_axis: str = "pipe"):
    """Wrap :func:`pipeline_grads_1f1b` in shard_map over ``mesh``.

    Takes GLOBAL arrays (``stage_params`` [S, ...] sharded over
    ``pipe_axis``; ``x`` [M, mb, ...] replicated) and returns
    ``(total_loss, grads)`` with grads in the same stage-stacked sharded
    layout as the params — ready for any :mod:`paddle_tpu.optim` rule.
    ``loss_fn(out_mb) -> scalar`` is the per-microbatch loss applied at the
    last stage (sum-reduced over microbatches)."""
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[pipe_axis]

    def inner(stage_params, x):
        squeezed = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        loss, grads = pipeline_grads_1f1b(stage_fn, loss_fn, squeezed, x,
                                          pipe_axis, S)
        return loss, jax.tree_util.tree_map(lambda g: g[None], grads)

    return jax.shard_map(inner, mesh=mesh, in_specs=(P(pipe_axis), P()),
                     out_specs=(P(), P(pipe_axis)))
