"""Device (XLA): seconds of set-up during which JAX was tracing, lowering
or compiling a program or reading one from the compile cache: the union
of the intervals of ``paddle_tpu/obs/xla_cache.py:compile_log()``'s rows
(``jax.monitoring``'s ``jaxpr_trace_duration``,
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``, which
holds the cache's retrieval) that ended before the window opened. A
union, because a function traced inside another's trace has a row inside
the outer one's interval."""


def read(ctx):
    try:
        from paddle_tpu.obs.xla_cache import compile_log
    except ImportError:
        return None                 # a program that keeps no compile log
    spans = sorted((r["t"] - r["seconds"], r["t"]) for r in compile_log()
                   if ctx.t_start <= r["t"] <= ctx.window[0])
    if not spans:
        return None
    total, (lo, hi) = 0.0, spans[0]
    for a, b in spans[1:]:
        if a > hi:
            total, lo, hi = total + hi - lo, a, b
        else:
            hi = max(hi, b)
    return total + hi - lo
