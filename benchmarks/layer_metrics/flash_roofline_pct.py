"""Kernels: the least time the chip could take for the flash attention of
the traced steps (the larger of FLOPs / peak and bytes / HBM peak, from
``costs.flash_cost``) over the device time of ``flash_fwd`` +
``flash_bwd_dq`` + ``flash_bwd_dkv`` in the trace."""

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(ctx):
    steps = ctx.facts.get("traced_steps")
    if not steps or ctx.trace is None:
        return None
    seconds = sum(ctx.trace["kernel_seconds"].get(k, 0.0) for k in KERNELS)
    if seconds <= 0:
        return None
    layers, heads, head_dim = ctx.cell.costs.attention_shape(ctx.dims)
    per_chip = ctx.facts["batch"] // ctx.device["count"]
    cost = ctx.costs.flash_cost(per_chip, heads, ctx.facts["seq_len"],
                                head_dim)
    least, _ = ctx.costs.roofline_seconds(cost["flops"], cost["bytes"],
                                          ctx.peaks)
    return 100.0 * least * layers * steps / seconds
