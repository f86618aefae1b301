"""3x3-conv campaign (VERDICT r4 #3): per-shape fwd/dx/dW cost + roofline.

The round-3 trace put the ResNet-50 bs128 step's 3x3 convs at 41-47% MXU
— never examined per shape. This experiment measures, for every 3x3 conv
of the bs128 step (and the 7x7 stem), the train-relevant triple
(forward + dx + dW via jax.vjp) under the interleaved-differential
protocol, and compares each against its compute/bandwidth ROOFLINE:
  t_floor = max(flops / bf16_peak, hbm_bytes / hbm_bw)
with hbm_bytes the compulsory traffic (x, w, y read+write once per pass
as touched by the fwd/dx/dW triple). measured/floor tells us whether a
hand kernel could exist; a ratio near 1 closes the door the way
conv1x1_backward.py closed the 1x1 one.

Run on the chip (one process holds it):
  PYTHONPATH=/root/repo python experiments/conv3x3_shapes.py
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

B = 128
K = 60
PEAK = 197e12          # v5e bf16
HBM_BW = 819e9         # v5e HBM GB/s

# (H_in, Cin, Cout, kernel, stride, count_in_model) — ResNet-50 bs128,
# stride lives in the 3x3 (models/resnet.py Bottleneck.c2)
SHAPES = [
    (224, 3, 64, 7, 2, 1),        # stem
    (56, 64, 64, 3, 1, 3),        # stage0 c2
    (56, 128, 128, 3, 2, 1),      # stage1 first c2
    (28, 128, 128, 3, 1, 3),      # stage1 c2
    (28, 256, 256, 3, 2, 1),      # stage2 first c2
    (14, 256, 256, 3, 1, 5),      # stage2 c2
    (14, 512, 512, 3, 2, 1),      # stage3 first c2
    (7, 512, 512, 3, 1, 2),       # stage3 c2
]


def conv(x, w, stride):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def timed(fn, x, w, dy):
    """ms per fwd+vjp pass, interleaved differential.

    NOTE: bench.py's run_timed_child is the CANONICAL implementation of
    this protocol (conv1x1_backward.py carries the same copy) — protocol
    fixes land there first; keep the experiment copies in sync."""

    @partial(jax.jit, static_argnames=("k",))
    def run(x, w, dy, k):
        def body(i, carry):
            acc, x, dy = carry
            y, vjp = jax.vjp(fn, x, w)
            dx, dw = vjp(dy)
            return (acc + jnp.sum(dw.astype(jnp.float32)),
                    x + 1e-12 * dx.astype(x.dtype),
                    dy + 1e-12 * y.astype(dy.dtype))
        acc, _, _ = lax.fori_loop(
            0, k, body, (jnp.zeros((), jnp.float32), x, dy))
        return acc

    for k in (K, 3 * K):
        run(x, w, dy, k).block_until_ready()

    def once(k):
        t0 = time.perf_counter()
        float(jax.device_get(run(x, w, dy, k)))
        return time.perf_counter() - t0

    once(K)
    t1, t2 = once(K), once(3 * K)
    if t2 <= t1:
        return None
    return (t2 - t1) / (2 * K) * 1e3


def main():
    rows = []
    for (h, cin, cout, kk, stride, count) in SHAPES:
        rng = np.random.RandomState(0)
        ho = h // stride
        x = jnp.asarray(rng.normal(size=(B, h, h, cin)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(kk, kk, cin, cout)) * 0.05,
                        jnp.bfloat16)
        dy = jnp.asarray(rng.normal(size=(B, ho, ho, cout)), jnp.bfloat16)
        fn = partial(conv, stride=stride)
        ms = timed(fn, x, w, dy)
        # fwd + dx + dW each do ~2*B*Ho*Wo*K*K*Cin*Cout FLOPs
        flops = 3 * 2.0 * B * ho * ho * kk * kk * cin * cout
        # compulsory HBM traffic over the triple (bf16=2B):
        #   fwd reads x,w writes y; dx reads dy,w writes dx(x-sized);
        #   dW reads x,dy writes dw  ->  3 x-sized + 3 y-sized + ~3 w
        bx = 2.0 * B * h * h * cin
        by = 2.0 * B * ho * ho * cout
        bw_ = 2.0 * kk * kk * cin * cout
        bytes_ = 3 * bx + 3 * by + 3 * bw_
        t_mxu = flops / PEAK * 1e3
        t_hbm = bytes_ / HBM_BW * 1e3
        floor = max(t_mxu, t_hbm)
        row = {"shape": f"{h}x{h}x{cin}->{cout} k{kk} s{stride}",
               "count": count,
               "ms": None if ms is None else round(ms, 3),
               "mxu_pct": None if ms is None else round(
                   100 * flops / (ms * 1e-3) / PEAK, 1),
               "floor_ms": round(floor, 3),
               "bound": "mxu" if t_mxu >= t_hbm else "hbm",
               "measured_over_floor": None if ms is None else round(
                   ms / floor, 2)}
        rows.append(row)
        print(json.dumps(row))
    ok = [r for r in rows if r["ms"] is not None]
    print(json.dumps({
        "total_step_ms": round(sum(r["ms"] * r["count"] for r in ok), 2),
        "total_floor_ms": round(
            sum(r["floor_ms"] * r["count"] for r in ok), 2),
        "device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
