"""Trainer loop: median, over the traced sub-window's steps, of the
program's own ``train_step`` span (``train/trainer.py:_run_pass``, one
iteration from the reader's ``yield`` to after ``EndIteration``) less its
``loss_fetch`` child (``float(loss)``, where the host waits for the
device): host time a step in which the chip is not waited for."""
import statistics


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
        [e for e in spans if e["name"] in ("train_step", "loss_fetch")])
        if r["name"] == "train_step"]
    return statistics.median(steps) if steps else None
