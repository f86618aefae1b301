"""Scheduler: mean wall of one ``sched.step()`` in the window less the
wall inside the engine calls it makes (``decode_tick``, ``begin_prefill``,
``prefill_step``, wrapped from outside)."""


def read(ctx):
    steps = ctx.in_window("sched_step")
    if not steps:
        return None
    inside = sum(sum(ctx.in_window(n))
                 for n in ("tick", "begin_prefill", "prefill_step"))
    return (sum(steps) - inside) / len(steps)
