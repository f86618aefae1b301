"""Trainer loop: median of the program's own ``dispatch`` span in the
traced sub-window (``train/trainer.py:_run_pass``, round the call of the
jitted step, which returns once XLA has the program; parameters and both Adam
moments cross the call, several hundred leaves)."""
import statistics


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer
    except ImportError:
        return None                 # a program without its own spans
    window = ctx.rec.spans.get("window")
    if not window:
        return None
    calls = [e["dur"] / 1e3 for e in session_tracer().between(
        *window[0][:2]) if e["name"] == "dispatch"]
    return statistics.median(calls) if calls else None
