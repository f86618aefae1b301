"""Kernels: the least time the chip could take for the routed experts'
grouped products of the traced sub-window (the weights of the (layer,
expert) sets the counters say were hit, read once a call, plus the pairs'
rows, against the HBM peak; three products a pair against the bf16 peak;
the larger, from the configuration's ``costs`` module: ``moe_ffn_cost``)
over the device time of the ``ragged-dot-none`` custom calls in the trace
(XLA's name for ``jax.lax.ragged_dot``'s grouped product on the TPU; the
``ragged-dot-metadata`` calls beside them are not the product).
Calls are the traced ticks (``engine_tick``'s facts) and the prompts
whose prefill ended in the window (``prefill_drain``'s facts, its chunks
summed)."""


def read(ctx):
    try:
        from paddle_tpu.obs.trace import session_tracer
    except ImportError:
        return None                 # a program without its own spans
    cost_fn = getattr(ctx.cell.costs, "moe_ffn_cost", None)
    window = ctx.rec.spans.get("window")
    if ctx.trace is None or cost_fn is None or not window:
        return None
    seconds = ctx.trace["kernel_seconds"].get("ragged-dot-none", 0.0)
    if seconds <= 0:
        return None
    least = 0.0
    for e in session_tracer().between(*window[0][:2]):
        facts = e.get("args", {})
        if e["name"] in ("engine_tick", "prefill_drain") \
                and facts.get("expert_pairs"):
            cost = cost_fn(ctx.dims, facts["expert_pairs"],
                           facts["expert_hits"])
            least += ctx.costs.roofline_seconds(
                cost["flops"], cost["bytes"], ctx.peaks)[0]
    return 100.0 * least / seconds if least else None
