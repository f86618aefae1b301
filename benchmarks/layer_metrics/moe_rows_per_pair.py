"""Model step: the rows the held experts' grouped products were handed
over the (token, expert) pairs they kept, on the traced window's ticks
and the prompts whose prefill ended in it: the facts ``expert_rows`` and
``expert_pairs`` the engine puts on its ``engine_tick`` and
``prefill_drain`` spans from the counters the compiled programs return
(``nn/moe.py:HeldExpertsFFN`` -> ``serve/engine.py:_count_experts``).
1 is a product with no padding; a product over every sorted pair of a
tick that keeps 2 rows for each of 16 held experts would read 16.
Nothing for a program that does not count the rows."""


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer
    except ImportError:
        return None                 # a program without its own spans
    window = ctx.rec.spans.get("window")
    if not window:
        return None
    rows = pairs = 0
    for e in session_tracer().between(*window[0][:2]):
        facts = e.get("args", {})
        if e["name"] in ("engine_tick", "prefill_drain") \
                and "expert_rows" in facts:
            rows += facts["expert_rows"]
            pairs += facts["expert_pairs"]
    return rows / pairs if pairs else None
