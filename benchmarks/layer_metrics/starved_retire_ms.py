"""Engine: seconds of the program's ``starved`` stretches in the traced
window whose innermost span (``obs/trace.py:starved_by_span``) is
``tick_retire`` (``serve/engine.py:decode_tick``), ``prefill_retire`` or
``prefill_drain``'s own time (``prefill_step``), or the scheduler's
``decode_tick`` less the engine's tick (``serve/scheduler.py:step``: the
delivery loop), over the window's ``engine_tick`` spans, in ms a tick:
results turned into tokens and requests while the chip waits."""

NAMES = ("tick_retire", "prefill_drain", "prefill_retire",
         "decode_tick")


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
