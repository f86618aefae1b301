"""Engine: median wall of one ``decode_tick()`` in the window (it drains
to the host, so the wall is fenced)."""
import statistics


def read(ctx):
    ticks = ctx.in_window("tick")
    return statistics.median(ticks) if ticks else None
