"""Scheduler: 95th percentile, over every request submitted in the
window, of submit to first token visible (``step()`` returned). In a
closed loop on as many clients as slots it is a tick plus one prefill for
nine requests in ten and a tick plus two for the tenth, so the 95th
percentile sits on the edge between the two and swings by a third from
seed to seed: recorded here, not held to a bound (PERF.md, section 6)."""


def read(ctx):
    return ctx.end_to_end.get("ttft_p95_ms")
