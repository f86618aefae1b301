"""Scheduler: ``ttft_p95_ms`` (submit to first token visible, 95th
percentile over the requests submitted in the window) in a cell that is
always full and judged on tokens per second: a prompt's chunks with the
ticks and the other admissions' chunks between them."""


def read(ctx):
    return ctx.end_to_end.get("ttft_p95_ms")
