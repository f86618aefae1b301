"""Model step: median, over the traced sub-window's ticks, of the busiest
held expert's tokens over the mean over all (expert layer, held expert)
pairs: the facts ``expert_max`` and ``expert_pairs`` the engine puts on
its ``engine_tick`` span from the counts the compiled tick returns
(``serve/engine.py:_count_experts``). 1 is an even load; the grouped
product's time follows the experts that were hit and the busiest one."""
import statistics


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer
    except ImportError:
        return None                 # a program without its own spans
    window = ctx.rec.spans.get("window")
    slots = ctx.facts.get("expert_slots")
    if not window or not slots:
        return None
    ratios = []
    for e in session_tracer().between(*window[0][:2]):
        facts = e.get("args", {})
        if e["name"] == "engine_tick" and facts.get("expert_pairs"):
            ratios.append(facts["expert_max"] * slots
                          / facts["expert_pairs"])
    return statistics.median(ratios) if ratios else None
