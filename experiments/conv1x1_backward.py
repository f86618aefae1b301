"""1x1-bottleneck conv backward: XLA conv path vs matmul form vs Pallas.

PERF.md (round 3) measured the ResNet-50 residual ceiling at XLA's conv
kernels: dW for [1,1,Cin,Cout] shapes at ~13% MXU, dx/BN-backward
mega-fusions at 5-11%. A 1x1 stride-1 conv IS a matmul
([B*H*W, Cin] @ [Cin, Cout]), and XLA's *matmul* path tiles these shapes
very differently from its conv path — so before hand-writing Pallas, this
experiment measures, per bottleneck shape of the bs128 step, the full
train-relevant cost (forward + dx + dW via jax.vjp) of:

  a. ``lax.conv_general_dilated`` (the shipped form);
  b. reshape + ``lax.dot_general`` (matmul form — its VJP is two matmuls);
  c. (when available) the Pallas dW kernel in
     ``paddle_tpu.nn.pallas_conv``.

Protocol: bf16 operands, fori_loop(K) chained inside ONE jit call so the
dispatch cost amortises; a single scalar fetch closes the timing
(the r4 no-fetch-inside-timing rule). Run on the real chip:
``python experiments/conv1x1_backward.py``.
"""

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

# the 1x1 convs of ResNet-50 bs128 @224 (NHWC): (H, Cin, Cout)
SHAPES = [
    (56, 64, 256),     # stage0 c3
    (56, 256, 64),     # stage0 c1 (later blocks)
    (28, 128, 512),    # stage1 c3
    (28, 512, 128),    # stage1 c1
    (14, 256, 1024),   # stage2 c3
    (14, 1024, 256),   # stage2 c1
    (7, 512, 2048),    # stage3 c3
    (7, 2048, 512),    # stage3 c1
]
B = 128
K = 200         # differential pair is (K, 3K) chained passes per jit call


def conv_form(x, w):
    return lax.conv_general_dilated(
        x, w.reshape(1, 1, w.shape[0], w.shape[1]),
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def matmul_form(x, w):
    b, h, ww, c = x.shape
    y = x.reshape(b * h * ww, c) @ w
    return y.reshape(b, h, ww, w.shape[1])


def timed(fn, x, w, dy):
    """ms per fwd+vjp pass, differential: time (dispatch + fetch) at K and
    3K chained passes inside one jit call each and difference — the
    fetch/dispatch constant cancels (same rule as bench.py).

    NOTE: bench.py's run_timed_child is the CANONICAL implementation of
    the interleaved-differential protocol; protocol fixes land there
    first — keep this experiment copy in sync when touching either."""

    @partial(jax.jit, static_argnames=("k",))
    def run(x, w, dy, k):
        def body(i, carry):
            # EVERY product must be loop-variant or XLA hoists it: y
            # feeds dy (keeps the forward alive and dx varying — dx of a
            # linear op does not depend on x!), dx feeds x, dw feeds acc.
            acc, x, dy = carry
            y, vjp = jax.vjp(fn, x, w)
            dx, dw = vjp(dy)
            return (acc + jnp.sum(dw.astype(jnp.float32)),
                    x + 1e-12 * dx.astype(x.dtype),
                    dy + 1e-12 * y.astype(dy.dtype))
        acc, _, _ = lax.fori_loop(
            0, k, body, (jnp.zeros((), jnp.float32), x, dy))
        return acc

    def once(k):
        t0 = time.perf_counter()
        float(jax.device_get(run(x, w, dy, k)))
        return time.perf_counter() - t0

    for k in (K, 3 * K):
        run(x, w, dy, k).block_until_ready()   # compile both variants
    once(K)                                    # warm
    t1, t2 = once(K), once(3 * K)
    if t2 <= t1:
        return None        # drift swamped the signal: say so, don't clamp
    return (t2 - t1) / (2 * K) * 1e3


def main():
    rows = []
    forms = {"conv": conv_form, "matmul": matmul_form}
    try:
        from paddle_tpu.nn import pallas_conv
        forms["pallas"] = pallas_conv.conv1x1
    except (ImportError, AttributeError):
        pass
    for (h, cin, cout) in SHAPES:
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.normal(size=(B, h, h, cin)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(cin, cout)) * 0.05, jnp.bfloat16)
        dy = jnp.asarray(rng.normal(size=(B, h, h, cout)), jnp.bfloat16)
        row = {"shape": f"{h}x{h}x{cin}->{cout}"}
        flops = 3 * 2.0 * B * h * h * cin * cout      # fwd+dx+dW
        for name, fn in forms.items():
            ms = timed(fn, x, w, dy)
            if ms is None:             # degenerate differential (drift)
                row[name + "_ms"] = None
                row[name + "_mxu_pct"] = None
                continue
            row[name + "_ms"] = round(ms, 3)
            row[name + "_mxu_pct"] = round(
                100 * flops / (ms * 1e-3) / 197e12, 1)
        rows.append(row)
        print(json.dumps(row))
    tot = {f: (round(sum(r[f + "_ms"] for r in rows), 3)
               if all(r[f + "_ms"] is not None for r in rows) else None)
           for f in forms}
    print(json.dumps({"total_ms_per_step_equivalent": tot}))


if __name__ == "__main__":
    main()
