"""Engine: median wall of one admission's prefill in the window
(``begin_prefill`` + ``prefill_step``; the first token is fetched, so the
wall is fenced)."""
import statistics


def read(ctx):
    begin = ctx.in_window("begin_prefill")
    step = ctx.in_window("prefill_step")
    if not step or len(begin) != len(step):
        return None
    return statistics.median(b + s for b, s in zip(begin, step))
