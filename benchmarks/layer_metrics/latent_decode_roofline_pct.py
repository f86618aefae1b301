"""Kernels: the least time the chip could take for the latent decode
attention of the traced ticks (each live cached row read ONCE for all
heads at the pool's dtype, plus q and out, against the HBM peak; the
absorbed scores and weighted sums against the bf16 peak; the larger, from
the configuration's ``costs`` module: ``latent_decode_cost``) over the
device time of the ``latent_decode`` kernel in the trace."""


def read(ctx):
    cost_fn = getattr(ctx.cell.costs, "latent_decode_cost", None)
    if ctx.trace is None or cost_fn is None:
        return None
    seconds = ctx.trace["kernel_seconds"].get("latent_decode", 0.0)
    ticks = ctx.rec.traced("tick")
    if seconds <= 0 or not ticks or "latent_width" not in ctx.facts:
        return None
    layers, heads, _ = ctx.cell.costs.attention_shape(ctx.dims)
    least = 0.0
    for _, _, facts in ticks:
        cost = cost_fn(facts["live_tokens"], ctx.facts["slots"], heads,
                       ctx.facts["latent_width"], ctx.dims.kv_rank,
                       ctx.facts["pool_bytes"])
        least += layers * ctx.costs.roofline_seconds(
            cost["flops"], cost["bytes"], ctx.peaks)[0]
    return 100.0 * least / seconds
