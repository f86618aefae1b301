"""Engine: 100 x the seconds of the program's ``starved`` stretches inside
the traced window (``serve/engine.py:DecodeEngine._fed``: from a drain's
fetch to the next compiled call's return, when nothing is queued on the
chip) over the window: the host-bound share by the program's own clock,
the inside twin of ``device_idle_pct.serve``."""


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer, starved_in_window
    except ImportError:
        return None                 # a program without starved stretches
    window = ctx.rec.spans.get("window")
    if not window:
        return None
    lo, hi = window[0][:2]
    by, _ = starved_in_window(session_tracer(), lo, hi)
    return 100.0 * sum(by.values()) / (hi - lo) if by else None
