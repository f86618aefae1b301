"""Driver of a training cell: ``Trainer.init`` then ONE ``Trainer.train``
call on a reader, the plain loop (``steps_per_call=1``, ``grad_accum=1``,
no remat), flash attention under ``bfloat16_compute``, Adam. The same
compiled step with the same state takes the seed's first steps (set-up:
they compile, and they are what the reference follows) and then the
measured window; the reader yields until the window's deadline.

Every size comes from the cell's files: the model from the configuration,
sequence length and token statistics from the traffic mix, the batch,
the optimizer and the limits from ``benchmarks/workloads/<cell>.json``.
Whatever depends on the architecture (the model, its loss, its weights
and their leaves, the reference) comes from the modules the configuration
names (``ctx.cell``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import harness, traffic

FOLLOWED_STEPS = 3         # the reference follows the first three


def build(ctx):
    """The model and the Trainer of the cell, nothing run yet."""
    from paddle_tpu import optim
    from paddle_tpu.core import mesh as mesh_lib
    from paddle_tpu.train import Trainer
    layout, opt = ctx.cell.layout, ctx.cell.file["train"]["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"optimizer {opt['name']!r}: only adam has a "
                         f"reference")
    mesh = (mesh_lib.single_device_mesh(ctx.devices[0])
            if len(ctx.devices) == 1
            else mesh_lib.make_mesh({"data": len(ctx.devices)},
                                    devices=ctx.devices))
    return Trainer(
        layout.build_model(ctx.dims),
        loss_fn=layout.loss_fn(ctx.dims),
        optimizer=optim.adam(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                             eps=opt["eps"]),
        mesh=mesh)


def install_weights(trainer, layout, z, seed: int) -> None:
    """Replace what ``Trainer.init`` drew by the benchmark's weights for
    the seed, leaf for leaf in the placement the Trainer chose."""
    import jax
    ts = trainer.train_state
    placement = jax.tree_util.tree_map(lambda a: a.sharding, ts.params)
    ts.params = None
    ts.params = jax.tree_util.tree_map(
        jax.device_put, layout.program_params(z, seed), placement)


def norm_programs(layout, z):
    """Two small jitted programs: per-leaf norms of a params-shaped tree,
    and per-leaf norms of (params - the seed's initial weights), the
    initial weights made again on the device and not kept."""
    import jax
    import jax.numpy as jnp
    tm = jax.tree_util.tree_map
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    norms = jax.jit(lambda tree: tm(norm, tree))
    change = jax.jit(lambda params, seed: tm(
        lambda a, b: norm(a - b), params, layout.seed_params(z, seed)))
    return norms, change


def as_program(layout, ref_result: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference's ``train_reference`` returns, in the shape of the
    program's readings: how the control and the planted faults are put in
    the program's place."""
    return {"losses": ref_result["losses"],
            "grad_norms": layout.flatten_reference(ref_result["grad_norms"]),
            "change_norms": layout.flatten_reference(
                ref_result["change_norms"])}


def compare(layout, program: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """The numbers that decide ``correct`` for a training cell, each
    beside its limit. Loss gaps are relative to the reference's loss.
    Norm gaps are taken by the worst leaf: |program's norm - reference's
    norm| over the larger of the reference's norm of that leaf and of the
    median leaf. Leaves whose reference gradient is under a thousandth of
    the median leaf's move under Adam by round-off alone and are left out
    of the change."""
    checks = []
    for i, (a, b) in enumerate(zip(program["losses"], ref["losses"]), 1):
        if f"loss{i}_gap" in limits:
            checks.append(harness.check(f"loss{i}_gap", abs(a - b) / abs(b),
                                        limits[f"loss{i}_gap"]))
    g_ref = layout.flatten_reference(ref["grad_norms"])
    c_ref = layout.flatten_reference(ref["change_norms"])
    g_med = float(np.median(list(g_ref.values())))
    live = [k for k in c_ref if g_ref[k] >= 1e-3 * g_med]
    c_med = float(np.median([c_ref[k] for k in live]))

    def worst(name, prog, ref_norms, keys, med):
        gap, leaf = max((abs(prog[k] - ref_norms[k])
                         / max(ref_norms[k], med), k) for k in keys)
        return dict(harness.check(name, gap, limits[name]), leaf=leaf)

    checks.append(worst("grad_norm_gap", program["grad_norms"], g_ref,
                        g_ref, g_med))
    checks.append(worst("change_norm_gap", program["change_norms"], c_ref,
                        live, c_med))
    return checks


def run(ctx) -> Dict[str, Any]:
    import jax
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    from paddle_tpu.train import events
    z, rec, spec = ctx.dims, ctx.rec, ctx.cell.file["train"]
    mix, layout = ctx.cell.traffic, ctx.cell.layout
    B, T = int(spec["batch"]), int(mix["seq_len"])
    warm = max(int(spec["warm_steps"]), FOLLOWED_STEPS + 1)
    b1 = float(spec["optimizer"]["b1"])
    stream = traffic.train_batches(mix, z.V, B, ctx.seed)
    followed = [next(stream) for _ in range(FOLLOWED_STEPS)]
    trainer = build(ctx)
    norms, change = norm_programs(layout, z)
    # a jit argument, not a constant: one program serves every seed
    seed32 = np.uint32(ctx.seed % 2 ** 32)
    stamps: List[float] = []
    losses: List[float] = []
    program: Dict[str, Any] = {}
    state = {"deadline": None, "t0": None, "i0": None, "traced_steps": 0}

    def reader():
        fed = 0
        while state["deadline"] is None \
                or time.perf_counter() < state["deadline"]:
            with rec.span("reader_next"):
                x, y = followed[fed] if fed < FOLLOWED_STEPS \
                    else next(stream)
                batch = {"x": x, "y": y}
            fed += 1
            rec.begin("trainer_step")
            yield batch

    def on_event(e):
        if not isinstance(e, events.EndIteration):
            return
        rec.end("trainer_step")
        now = time.perf_counter()
        with rec.span("event_handler"):
            stamps.append(now)
            losses.append(float(e.cost))
            n = len(stamps)
            ts = trainer.train_state
            if n == 1:
                # Adam's first moment after one step is (1 - b1) * g1
                m = layout.flatten_program(jax.device_get(
                    norms(ts.opt_state.m)))
                program["grad_norms"] = {k: v / (1.0 - b1)
                                         for k, v in m.items()}
            if n == FOLLOWED_STEPS:
                program["change_norms"] = layout.flatten_program(
                    jax.device_get(change(ts.params, seed32)))
            if rec.tracing:
                state["traced_steps"] += 1
            if n >= warm and state["t0"] is None:
                # a traced run takes its trace FIRST, then opens the window
                prof = ctx.profile
                if ctx.trace_on and not prof.started:
                    prof.start()
                else:
                    prof.tick()
                if not ctx.trace_on or prof.done:
                    state["i0"] = n - 1
                    state["t0"] = stamps[-1] = time.perf_counter()
                    state["deadline"] = state["t0"] + ctx.seconds

    with use_policy(bfloat16_compute):
        with rec.span("trainer_init"):
            x0, y0 = followed[0]
            trainer.init(jax.random.PRNGKey(0), {"x": x0, "y": y0})
            install_weights(trainer, layout, z, ctx.seed)
        trainer.train(reader, num_passes=1, event_handler=on_event,
                      log_period=0)
    i0 = state["i0"]                     # the stamp that opened the window
    ctx.window = (stamps[i0], stamps[-1])
    steps = len(stamps) - 1 - i0
    tokens = steps * B * T
    program["losses"] = losses[:FOLLOWED_STEPS]
    ctx.facts.update(batch=B, seq_len=T, steps=steps, tokens=tokens,
                     traced_steps=state["traced_steps"],
                     step_ms=list(np.diff(stamps[i0:]) * 1e3))
    t_init = rec.spans["trainer_init"][0]
    handler = [round(t1 - t0, 2) for t0, t1, _ in
               rec.spans["event_handler"][:warm]]
    harness.log(f"train: set-up: before Trainer.init "
                f"{t_init[0] - ctx.t_start:.1f}s, init + weights "
                f"{t_init[1] - t_init[0]:.1f}s, first {warm} steps end at "
                f"{[round(s - ctx.t_start, 1) for s in stamps[:warm]]}s "
                f"(handlers {handler}s)")
    harness.log(f"train: batch {B} x {T}, {steps} steps in "
                f"{ctx.window_s:.3f}s after {warm} warm-up steps, losses "
                f"{[round(l, 4) for l in losses[:4]]} .. "
                f"{losses[-1]:.4f}")
    peak = harness.memory_peak_bytes(ctx.devices)
    finite = bool(np.isfinite(losses).all())
    trainer.train_state = None
    del trainer
    harness.free_device_memory()
    t_ref = time.perf_counter()
    ref = ctx.cell.reference.train_reference(ctx.cell.config, ctx.seed,
                                             followed, spec["optimizer"])
    checks = compare(layout, program, ref, ctx.cell.file["limits"])
    ctx.facts.update(followed=followed, reference=ref)
    harness.log(f"train: reference followed {FOLLOWED_STEPS} steps in "
                f"{time.perf_counter() - t_ref:.1f}s, losses "
                f"{[round(l, 4) for l in ref['losses']]}")
    return {"metrics": {"train_tokens_per_s": tokens / ctx.window_s},
            "attempted": steps, "failed": 0 if finite else steps,
            "checks": checks, "memory_peak_bytes": peak,
            "correct": finite and steps > 0
            and all(c["ok"] for c in checks)}
