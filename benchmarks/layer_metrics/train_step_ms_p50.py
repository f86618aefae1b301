"""Trainer loop: median time between consecutive ``EndIteration`` stamps
in the window (the harness's host clock)."""
import statistics


def read(ctx):
    steps = ctx.facts.get("step_ms")
    return statistics.median(steps) if steps else None
