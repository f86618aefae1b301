"""Engine: seconds of the program's ``starved`` stretches in the traced
window whose innermost span (``obs/trace.py:starved_by_span``) is
``tick_stage``, ``tick_dispatch`` (``serve/engine.py:decode_tick``),
``begin_prefill`` or ``prefill_dispatch`` (``begin_prefill``,
``prefill_step``), over the window's ``engine_tick`` spans, in ms a tick:
the host building and enqueuing programs while the chip waits."""

NAMES = ("tick_stage", "tick_dispatch", "begin_prefill",
         "prefill_dispatch")


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
