"""Scheduler: median, over the traced sub-window's steps, of the program's
own ``sched_step`` span (``serve/scheduler.py:step``) less every engine
span under it (``begin_prefill``, ``prefill_dispatch``, ``prefill_drain``,
``engine_tick``, opened in ``serve/engine.py``): the scheduler's own host
time a step, the inside twin of ``sched_overhead_ms``."""
import statistics

ENGINE = ("begin_prefill", "prefill_dispatch", "prefill_drain",
          "engine_tick")


def read(ctx):
    try:
        from paddle_tpu.obs.trace import self_times, session_tracer
    except ImportError:
        return None                 # a program without its own spans
    window = ctx.rec.spans.get("window")
    if not window:
        return None
    spans = session_tracer().between(*window[0][:2])
    steps = [r["self"] / 1e3 for r in self_times(
        [e for e in spans if e["name"] == "sched_step"
         or e["name"] in ENGINE]) if r["name"] == "sched_step"]
    return statistics.median(steps) if steps else None
