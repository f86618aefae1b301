"""Engine: median, over the traced sub-window's admissions, of
``begin_prefill`` (``serve/engine.py``: block reservation, prefix lookup)
+ ``prefill_chunk`` (``serve/scheduler.py:_advance_prefill``, round
``engine.prefill_step``) less ``prefill_dispatch`` and ``prefill_drain``
inside it (the enqueue and the wait for the first token): what an
admission costs the host beside feeding and waiting for the chip."""
import statistics

NAMES = ("begin_prefill", "prefill_chunk", "prefill_dispatch",
         "prefill_drain")


def read(ctx):
    try:
        from paddle_tpu.obs.trace import self_times, session_tracer
    except ImportError:
        return None                 # a program without its own spans
    window = ctx.rec.spans.get("window")
    if not window:
        return None
    spans = session_tracer().between(*window[0][:2])
    admissions, open_on = [], {}    # slot -> index of its open admission
    for r in self_times([e for e in spans if e["name"] in NAMES]):
        slot = (r.get("args") or {}).get("slot")
        if r["name"] == "begin_prefill":
            open_on[slot] = len(admissions)
            admissions.append(r["self"] / 1e3)
        elif r["name"] == "prefill_chunk" and slot in open_on:
            admissions[open_on[slot]] += r["self"] / 1e3
    return statistics.median(admissions) if admissions else None
