"""Engine: median, over the traced sub-window's ticks, of ``tick_stage``
+ ``tick_retire`` inside one ``engine_tick``
(``serve/engine.py:decode_tick``): the guard, the block tables and the
operands before the enqueue, and the per-slot loop after the drain: the
tick's host-only part, in which the chip is neither fed nor waited for."""
import statistics


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer
    except ImportError:
        return None                 # a program without its own spans
    window = ctx.rec.spans.get("window")
    if not window:
        return None
    spans = session_tracer().between(*window[0][:2])
    parts = [e for e in spans if e["name"] in ("tick_stage", "tick_retire")]
    ticks = []
    for t in (e for e in spans if e["name"] == "engine_tick"):
        end = t["ts"] + t["dur"]
        ticks.append(sum(p["dur"] for p in parts if p["tid"] == t["tid"]
                         and t["ts"] <= p["ts"] and p["ts"] + p["dur"] <= end)
                     / 1e3)
    return statistics.median(ticks) if ticks else None
