"""Model step: model FLOPs of forward and backward (from the
configuration's shapes, no recomputation) of every token trained in the
window, over window x chips x the chip's bf16 peak."""


def read(ctx):
    tokens = ctx.facts.get("tokens")
    if not tokens:
        return None
    flops = tokens * ctx.cell.costs.train_flops_per_token(
        ctx.dims, ctx.facts["seq_len"])
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.device["count"]
    return 100.0 * flops / (ctx.window_s * peak)
