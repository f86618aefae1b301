"""Model step: of the choices the traced ticks' live tokens made (tokens x
the router's choices a token x expert layers), the share that went to
identity ("zero-computation") experts and so cost no expert's weights:
the fact ``zero_pairs`` the engine puts on its ``engine_tick`` span from
the counters the compiled tick returns (``nn/moe.py:HeldExpertsFFN`` ->
``serve/engine.py:_count_experts``), over the same span's ``tokens``. A
router that chooses evenly over 512 real and 256 identity outputs reads
33.3; higher is less work a token."""


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer
    except ImportError:
        return None                 # a program without its own spans
    window = ctx.rec.spans.get("window")
    per_token = ctx.facts.get("top_k", 0) * ctx.facts.get("expert_layers", 0)
    if not window or not per_token:
        return None
    zero = choices = 0
    for e in session_tracer().between(*window[0][:2]):
        facts = e.get("args", {})
        if e["name"] == "engine_tick" and "zero_pairs" in facts:
            zero += facts["zero_pairs"]
            choices += facts["tokens"] * per_token
    return 100.0 * zero / choices if choices else None
