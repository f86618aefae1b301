"""Kernels: the least time the chip could take for the decode attention of
the traced ticks over GROUPED KV heads and two kinds of layer (the K and V
rows a layer really reads at the pool's dtype: every live position on a
full layer, a slot's window of them on a sliding one, read once for all
the query heads of a KV head, plus q and out, against the HBM peak; FLOPs
of every query head against the bf16 peak; the larger, from the
configuration's ``costs`` module: ``grouped_decode_cost``) over the device
time of ``paged_decode`` in the trace. The ticks' rows are the facts
``live_tokens`` and ``live_tokens_<group>`` the engine puts on its
``engine_tick`` span; the groups' layers and heads are
``layout.engine_facts``'s. Nothing for a program without pool groups."""


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer
    except ImportError:
        return None                 # a program without its own spans
    cost_fn = getattr(ctx.cell.costs, "grouped_decode_cost", None)
    groups = ctx.facts.get("pool_groups")
    window = ctx.rec.spans.get("window")
    if ctx.trace is None or cost_fn is None or not groups or not window:
        return None
    seconds = ctx.trace["kernel_seconds"].get("paged_decode", 0.0)
    if seconds <= 0:
        return None
    least = 0.0
    for e in session_tracer().between(*window[0][:2]):
        facts = e.get("args", {})
        if e["name"] != "engine_tick" or "live_tokens" not in facts:
            continue
        for name, group in groups.items():
            rows = facts.get(f"live_tokens_{name}" if group["window"]
                             else "live_tokens")
            if rows is None:
                return None
            cost = cost_fn(rows, facts["active"],
                           ctx.facts["query_heads"][name],
                           ctx.facts["kv_heads"], ctx.facts["head_dim"],
                           ctx.facts["pool_bytes"])
            least += group["layers"] * ctx.costs.roofline_seconds(
                cost["flops"], cost["bytes"], ctx.peaks)[0]
    return 100.0 * least / seconds if least else None
