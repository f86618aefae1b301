"""Scheduler: the 95th percentile of the gap between two tokens of one
request (what ``itl_p95_ms`` is end to end elsewhere) in a cell that is
always full. A gap is a tick and one prefill chunk of EVERY admission in
progress; with 0.4 chunks a tick two admissions overlap in about one
step in fifteen, so the 95th percentile rests on the hundred-odd such
steps of a window and swings by several percent from seed to seed:
recorded here, not held to a bound (PERF.md, section 6)."""


def read(ctx):
    return ctx.end_to_end.get("itl_p95_ms")
