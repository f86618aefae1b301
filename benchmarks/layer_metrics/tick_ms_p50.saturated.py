"""Engine: ``tick_ms_p50`` (median wall of one ``decode_tick()`` in the
window) in a cell that is judged on tokens per second and not on its
inter-token tail: a closed loop on as many callers as slots, always
full, where the tick's length is what a token a slot costs."""
import statistics


def read(ctx):
    ticks = ctx.in_window("tick")
    return statistics.median(ticks) if ticks else None
