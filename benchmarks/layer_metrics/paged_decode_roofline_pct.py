"""Kernels: the least time the chip could take for the paged attention of
the traced ticks (K and V rows of the live tokens at the pool's dtype
plus q and out, against the HBM peak; FLOPs against the bf16 peak; the
larger, from ``costs.paged_decode_cost``) over the device time of
``paged_decode`` in the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace["kernel_seconds"].get("paged_decode", 0.0)
    ticks = ctx.rec.traced("tick")
    if seconds <= 0 or not ticks:
        return None
    layers, heads, head_dim = ctx.cell.costs.attention_shape(ctx.dims)
    least = 0.0
    for _, _, facts in ticks:
        cost = ctx.costs.paged_decode_cost(
            facts["live_tokens"], ctx.facts["slots"], heads, head_dim,
            ctx.facts["pool_bytes"])
        least += layers * ctx.costs.roofline_seconds(
            cost["flops"], cost["bytes"], ctx.peaks)[0]
    return 100.0 * least / seconds
