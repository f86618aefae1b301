"""Engine: seconds of the program's ``starved`` stretches in the traced
window whose innermost span (``obs/trace.py:starved_by_span``) is
``tick_fetch`` or ``prefill_fetch`` (``serve/engine.py:decode_tick``,
``prefill_step``: the copies after the wait, a routed model's counters
in a second round trip), over the window's ``engine_tick`` spans, in ms
a tick: results copied to the host while the chip waits."""

NAMES = ("tick_fetch", "prefill_fetch")


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer, starved_in_window
    except ImportError:
        return None                 # a program without starved stretches
    window = ctx.rec.spans.get("window")
    if not window:
        return None
    by, ticks = starved_in_window(session_tracer(), *window[0][:2])
    return 1e3 * sum(by.get(n, 0.0) for n in NAMES) / ticks \
        if by and ticks else None
