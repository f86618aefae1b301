"""Where do the seq2seq bench's ms go? (round-5 MFU campaign, VERDICT #2)

bench seq2seq (B=64, src=tgt=30, h=512, e=256, V=30k, bf16) measured
10.37 ms/step = 12.0% MFU in round 4 and had never been profiled. This
script ablates the exact bench step on the real chip: full step, grad-only,
forward-only, encoder / decoder-scan / readout in isolation, the bare
scan-iteration overhead floor, and the batched-GEMM floor of the same
FLOPs. Results + conclusions land in PERF.md (older installation) "Round 5".

Usage: PYTHONPATH=/root/repo python experiments/profile_seq2seq.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

B, TS, TT, H, E, V = 64, 30, 30, 512, 256, 30000
PEAK = 197e12
K = 20          # steps per timed call


def timeit(fn, state, reps=3):
    """Interleaved-differential per-step seconds: alternate fori_loop
    regions of K and 3K steps; (T_3K - T_K)/(2K) cancels the
    per-dispatch constant, which otherwise floors every ablation
    identically (bench.py's protocol)."""
    stepk = jax.jit(lambda s: lax.fori_loop(0, K, lambda i, t: fn(t), s))
    step3k = jax.jit(lambda s: lax.fori_loop(0, 3 * K,
                                             lambda i, t: fn(t), s))

    def fence(s):
        # a real FETCH of the scalar accumulator (every ablation carries
        # it LAST, computed from the FULL result so DCE cannot hollow the
        # ablation out) closes the region
        return float(np.asarray(
            jax.device_get(jax.tree_util.tree_leaves(s)[-1])))

    s = step3k(stepk(state))                      # compile both + warm
    fence(s)
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        s = stepk(s)
        fence(s)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        s = step3k(s)
        fence(s)
        t3 = time.perf_counter() - t0
        samples.append((t3 - t1) / (2 * K))
    return sorted(samples)[len(samples) // 2]


def main():
    from paddle_tpu import optim
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    from paddle_tpu.models import Seq2SeqAttention
    from paddle_tpu.nn import costs
    from paddle_tpu.optim.optimizers import apply_updates

    rng = np.random.RandomState(0)
    batch = {
        "src": jnp.asarray(rng.randint(3, V, (B, TS)), jnp.int32),
        "src_len": jnp.full((B,), TS, jnp.int32),
        "tgt": jnp.asarray(rng.randint(3, V, (B, TT + 1)), jnp.int32),
        "tgt_len": jnp.full((B,), TT, jnp.int32),
    }
    model = Seq2SeqAttention(V, V, emb_dim=E, hidden=H)
    results = {}
    with use_policy(bfloat16_compute):
        variables = model.init(jax.random.PRNGKey(0), batch)
        opt = optim.adam(1e-3)
        opt_state = opt.init(variables["params"])
        p0 = variables["params"]

        def loss_of(p):
            return jnp.sum(model.apply({"params": p}, batch,
                                       train=True)) / (B * TT)

        # Component ablations keep params CONSTANT, so the iteration input
        # must change or XLA hoists the whole body out of the fori_loop
        # (loop-invariant code motion — caught in this script's second
        # run: forward "took" 5 us). A batch-axis roll by the running
        # shift is cheap and defeats hoisting.
        def loss_rolled(p, shift):
            b2 = dict(batch,
                      src=jnp.roll(batch["src"], shift, 0),
                      tgt=jnp.roll(batch["tgt"], shift, 0))
            return jnp.sum(model.apply({"params": p}, b2,
                                       train=True)) / (B * TT)

        # 1. full train step
        def full(s):
            p, o, n, _ = s
            l, g = jax.value_and_grad(loss_of)(p)
            u, o2 = opt.update(g, o, p, n)
            return (apply_updates(p, u), o2, n + 1, l)
        results["full_step"] = timeit(
            full, (p0, opt_state, jnp.zeros((), jnp.int32),
                   jnp.zeros((), jnp.float32)))

        # 2. value_and_grad only (no optimizer) — the grads must feed the
        # accumulator or XLA dead-code-eliminates the whole backward
        def vg(s):
            sh, acc = s
            l, g = jax.value_and_grad(loss_rolled)(p0, sh)
            gsum = sum(jnp.sum(x.astype(jnp.float32))
                       for x in jax.tree_util.tree_leaves(g))
            return (sh + 1, acc + l + 1e-12 * gsum)
        results["value_and_grad"] = timeit(
            vg, (jnp.zeros((), jnp.int32), jnp.zeros(())))

        # 3. forward only
        def fwd(s):
            sh, acc = s
            return (sh + 1, acc + loss_rolled(p0, sh))
        results["forward"] = timeit(
            fwd, (jnp.zeros((), jnp.int32), jnp.zeros(())))

        # 4. encoder only (BiGRU + masks + boot)
        def enc_only(s):
            sh, acc = s
            enc, m, d0 = model.apply({"params": p0},
                                     jnp.roll(batch["src"], sh, 0),
                                     batch["src_len"], method="encode")
            return (sh + 1, acc + jnp.sum(enc.astype(jnp.float32))
                    + jnp.sum(d0.astype(jnp.float32)))
        results["encoder_fwd"] = timeit(
            enc_only, (jnp.zeros((), jnp.int32), jnp.zeros(())))

        # 5. readout GEMM alone at the hoisted shape [B*TT, H] @ [H, V]
        w = jnp.asarray(rng.normal(size=(H, V)).astype(np.float32) * 0.02,
                        jnp.bfloat16)
        xro = jnp.asarray(rng.normal(size=(B * TT, H)), jnp.bfloat16)

        def ro(s):
            x, acc = s
            y = x @ w
            # fold a hash of the output back into x: chains iterations
            # (x stays bf16 — the bench-shape dtype; an f32 x measured
            # the wrong GEMM in this script's first committed run)
            x2 = x + (jnp.sum(y.astype(jnp.float32)) * 1e-24).astype(x.dtype)
            return (x2, acc + jnp.sum(y.astype(jnp.float32)))
        results["readout_gemm_fwd"] = timeit(ro, (xro, jnp.zeros(())))

        # 6. bare scan-iteration floor: TT iterations, one [B,H]@[H,H]
        wloop = jnp.asarray(rng.normal(size=(H, H)).astype(np.float32) * 0.02,
                            jnp.bfloat16)

        def bare(s):
            h, acc = s

            def body(c, _):
                return jnp.tanh(c @ wloop), ()
            h2, _ = lax.scan(body, h, None, length=TT)
            return (h2, acc + jnp.sum(h2.astype(jnp.float32)))
        results["bare_scan_30x_512gemm"] = timeit(
            bare, (jnp.asarray(rng.normal(size=(B, H)), jnp.bfloat16),
                   jnp.zeros(())))

    from bench import seq2seq_train_flops
    flops = seq2seq_train_flops(B, TS, TT, E, H, V)
    out = {k: round(v * 1e3, 3) for k, v in results.items()}
    out["train_flops"] = flops
    out["mfu_pct_full"] = round(100 * flops / results["full_step"] / PEAK, 2)
    out["device"] = jax.devices()[0].device_kind
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
