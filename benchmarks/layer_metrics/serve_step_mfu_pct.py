"""Model step: model FLOPs of the tokens really processed in the window
(each prefill at its true length, one forward per output token at its
context) over window x chips x the chip's bf16 peak."""


def read(ctx):
    if "contexts" not in ctx.facts or ctx.window_s <= 0:
        return None
    flops = ctx.cell.costs.serve_flops(ctx.dims, ctx.facts["prompts"],
                                       ctx.facts["contexts"])
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.device["count"]
    return 100.0 * flops / (ctx.window_s * peak) if flops else None
