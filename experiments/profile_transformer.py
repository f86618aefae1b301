"""Where do the compute-bound transformer's ms go? (round-4 MFU work)

bench transformer_big (d1024 L8 bs16 seq2048 bf16 flash) measured
0.95 s/step = 15.8% MFU — low for a GEMM-dominated config. This script
ablates the step on the real chip with the r4 interleaved-differential
protocol (no fetch inside timed regions):

  - full train step (fwd+bwd+adam)
  - value_and_grad only
  - forward only
  - attention isolated: flash fwd / flash fwd+bwd vs the dense reference
    at the bench shape, over the block_q/block_k grid
  - GEMM floor: the step's matmuls alone (QKVO + FFN + head as plain
    jnp.dot chains at identical shapes/dtypes)

Usage: PYTHONPATH=/root/repo python
       experiments/profile_transformer.py [--quick]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from _timing import diff_time

# PROF_* env overrides re-point the script at other transformer shapes
# (d512 bench config, d2048 scaling anchor). PROF_HEADS at fixed D is the
# dh=128 vs dh=64 MXU geometry experiment; attention FLOPs are
# H-independent.
B = int(os.environ.get("PROF_BS", "16"))
T = 2048
D = int(os.environ.get("PROF_DIM", "1024"))
L = int(os.environ.get("PROF_LAYERS", "8"))
H = int(os.environ.get("PROF_HEADS", "8"))
V = 32000
FFN = 4 * D
PEAK = 197e12


def main():
    from paddle_tpu import optim
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.nn import costs
    from paddle_tpu.nn.pallas_attention import (flash_attention,
                                                reference_attention)
    from paddle_tpu.optim.optimizers import apply_updates

    quick = "--quick" in sys.argv
    # --only fwd,att,ref,gemm,grad,full,dh128 runs a subset (each section
    # can run in a fresh process)
    only = None
    for a in sys.argv[1:]:
        if a.startswith("--only="):
            only = set(a.split("=", 1)[1].split(","))

    def want(sec):
        return only is None or sec in only

    out = {"config": f"d{D} L{L} bs{B} seq{T} bf16"}
    rng = np.random.RandomState(0)

    with use_policy(bfloat16_compute):
        model = TransformerLM(vocab=V, dim=D, num_layers=L, num_heads=H,
                              ffn_hidden=FFN, max_len=T, use_flash=True)
        ids = jnp.asarray(rng.randint(0, V, (B, T + 1)), jnp.int32)
        inp, tgt = ids[:, :-1], ids[:, 1:]
        variables = model.init(jax.random.PRNGKey(0), inp)
        opt = optim.adam(1e-4)
        params = variables["params"]
        opt_state = opt.init(params)

        def loss_of(p):
            logits = model.apply({"params": p}, inp)
            return jnp.mean(costs.softmax_cross_entropy(
                logits.reshape(-1, V), tgt.reshape(-1)))

        # Params must be STATE, never closure: a ~0.5 GB closure constant
        # blows up the remote-compile payload (reproducible broken pipe),
        # while the same program with params as donated arguments compiles
        # fine. Each section re-inits its own (donated) copy.

        # -- forward only ----------------------------------------------------
        def fwd_body(s):  # noqa: E306
            # folding 1e-20*loss into the params keeps them loop-variant
            # (no cross-call caching games) at far-below-bf16 resolution
            p, acc = s
            l = loss_of(p)
            p2 = jax.tree_util.tree_map(
                lambda a: a + (l * 1e-20).astype(a.dtype), p)
            return (p2, acc + l)

        if want("fwd"):
            out["fwd_only_ms"] = round(
                diff_time(fwd_body, (params, jnp.zeros((), jnp.float32)),
                          k=4), 1)
            print("partial:", json.dumps(out), file=sys.stderr, flush=True)

        # -- attention isolated ---------------------------------------------
        q_host = rng.normal(size=(B, H, T, D // H))

        def fresh_q():       # each diff_time donates its state
            return (jnp.asarray(q_host, jnp.bfloat16),
                    jnp.zeros((), jnp.float32))

        def att_cfg(bq, bk, with_bwd):
            def body(s):
                qq, acc = s
                if with_bwd:
                    def f(qq):
                        o = flash_attention(qq, qq, qq, causal=True,
                                            block_q=bq, block_k=bk)
                        return jnp.sum(o.astype(jnp.float32) ** 2)
                    l, dq = jax.value_and_grad(f)(qq)
                    return (qq + 1e-6 * dq.astype(qq.dtype), acc + l)
                o = flash_attention(qq, qq, qq, causal=True,
                                    block_q=bq, block_k=bk)
                return (qq + 1e-6 * o, acc + jnp.sum(o.astype(jnp.float32)))
            return body

        if want("att"):
            grid = [(128, 128)] if quick else [(128, 128), (256, 256),
                                               (512, 512), (256, 1024),
                                               (512, 1024), (1024, 1024)]
            att = {}
            for bq, bk in grid:
                att[f"fwd_bq{bq}_bk{bk}"] = round(
                    diff_time(att_cfg(bq, bk, False), fresh_q(), k=30,
                              use_fori=True), 2)
                att[f"fwdbwd_bq{bq}_bk{bk}"] = round(
                    diff_time(att_cfg(bq, bk, True), fresh_q(), k=30,
                              use_fori=True), 2)
            out["attention_per_layer_ms"] = att
            print("partial:", json.dumps(out), file=sys.stderr, flush=True)

        # -- dh=128 head-geometry probe (same total D = H*dh, same FLOPs):
        # at dh=64 both attention matmuls run half-width MXU tiles
        # (contraction / output dim 64 vs the 128x128 array) --------------
        if want("dh128") and D // H == 64:
            # only meaningful from the dh=64 geometry (PROF_HEADS=16 at
            # d1024); from the dh=128 default it would probe dh=256
            q128 = rng.normal(size=(B, H // 2, T, 2 * (D // H)))
            dh = {}
            for bq, bk in [(512, 1024), (1024, 1024)]:
                def cfg(with_bwd, bq=bq, bk=bk):
                    def body(s):
                        qq, acc = s
                        if with_bwd:
                            def f(qq):
                                o = flash_attention(qq, qq, qq, causal=True,
                                                    block_q=bq, block_k=bk)
                                return jnp.sum(o.astype(jnp.float32) ** 2)
                            l, dq = jax.value_and_grad(f)(qq)
                            return (qq + 1e-6 * dq.astype(qq.dtype), acc + l)
                        o = flash_attention(qq, qq, qq, causal=True,
                                            block_q=bq, block_k=bk)
                        return (qq + 1e-6 * o,
                                acc + jnp.sum(o.astype(jnp.float32)))
                    return body
                st = (jnp.asarray(q128, jnp.bfloat16),
                      jnp.zeros((), jnp.float32))
                dh[f"fwd_bq{bq}_bk{bk}"] = round(
                    diff_time(cfg(False), st, k=30, use_fori=True), 2)
                st = (jnp.asarray(q128, jnp.bfloat16),
                      jnp.zeros((), jnp.float32))
                dh[f"fwdbwd_bq{bq}_bk{bk}"] = round(
                    diff_time(cfg(True), st, k=30, use_fori=True), 2)
            out["attention_dh128_per_layer_ms"] = dh
            print("partial:", json.dumps(out), file=sys.stderr, flush=True)

        # dense reference attention (materialises [T,T]) for context
        def ref_body(s):
            qq, acc = s
            o = reference_attention(
                qq.astype(jnp.float32), qq.astype(jnp.float32),
                qq.astype(jnp.float32), causal=True)
            return (qq + 1e-6 * o.astype(qq.dtype),
                    acc + jnp.sum(o))
        if not quick and want("ref"):
            out["attention_ref_fwd_ms"] = round(
                diff_time(ref_body, fresh_q(), k=6,
                          use_fori=True), 2)

        # -- GEMM floor ------------------------------------------------------
        x2 = jnp.asarray(rng.normal(size=(B * T, D)), jnp.bfloat16)
        wq = jnp.asarray(rng.normal(size=(D, 3 * D)) * .02, jnp.bfloat16)
        wo = jnp.asarray(rng.normal(size=(D, D)) * .02, jnp.bfloat16)
        w1 = jnp.asarray(rng.normal(size=(D, FFN)) * .02, jnp.bfloat16)
        w2 = jnp.asarray(rng.normal(size=(FFN, D)) * .02, jnp.bfloat16)
        wh = jnp.asarray(rng.normal(size=(D, V)) * .02, jnp.bfloat16)

        def gemm_body(s):
            # weights ride in the state (donated): big closures break the
            # remote-compile payload
            x, acc, wq, wo, w1, w2, wh = s
            h = x
            for _ in range(L):
                h = (h @ wq)[:, :D]
                h = h @ wo
                h = jnp.maximum(h @ w1, 0) @ w2
            lg = h @ wh
            return (x + 1e-6 * h, acc + jnp.sum(lg.astype(jnp.float32)),
                    wq, wo, w1, w2, wh)

        if want("gemm"):
            out["gemm_fwd_floor_ms"] = round(
                diff_time(gemm_body,
                          (x2, jnp.zeros((), jnp.float32), wq, wo, w1, w2,
                           wh),
                          k=10, use_fori=True), 1)
            print("partial:", json.dumps(out), file=sys.stderr, flush=True)

        # -- grad only (fresh params, donated; SGD-like fold keeps every
        # grad leaf live) -----------------------------------------------------
        def grad_body(s):
            p, acc = s
            l, g = jax.value_and_grad(loss_of)(p)
            p2 = jax.tree_util.tree_map(
                lambda a, b: a - 1e-12 * b.astype(a.dtype), p, g)
            return (p2, acc + l)

        if want("grad"):
            params = model.init(jax.random.PRNGKey(0), inp)["params"]
            out["grad_only_ms"] = round(
                diff_time(grad_body, (params, jnp.zeros((), jnp.float32)),
                          k=4), 1)
            print("partial:", json.dumps(out), file=sys.stderr, flush=True)

        # -- full step (params were donated above: fresh init) ---------------
        def full_body(s):
            p, o, i, _ = s
            l, g = jax.value_and_grad(loss_of)(p)
            u, o2 = opt.update(g, o, p, i)
            return (apply_updates(p, u), o2, i + 1, l)

        if want("full"):
            params = model.init(jax.random.PRNGKey(0), inp)["params"]
            opt_state = opt.init(params)
            st = (params, opt_state, jnp.zeros((), jnp.int32),
                  jnp.zeros((), jnp.float32))
            out["full_step_ms"] = round(diff_time(full_body, st, k=4), 1)

            import bench
            flops = bench.transformer_train_flops(B, T, D, L, V, FFN)
            out["flops_per_step"] = flops
            out["mfu_from_full_step"] = round(
                100 * flops / (out["full_step_ms"] / 1e3) / PEAK, 1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
