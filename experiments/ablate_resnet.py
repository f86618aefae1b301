"""ResNet-50 step ablations: dispatch amortization (fori_loop) and batch size.

Compares wall-clock per train step for:
  - per-call dispatch (one jit call per step, chained donated state)
  - k steps per jit call via lax.fori_loop (amortizes the per-call
    dispatch overhead)

Usage: PYTHONPATH=.:$PYTHONPATH python experiments/ablate_resnet.py
"""

import json
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.obs import xla_cache

xla_cache.setup()


def build(batch_size, stem="conv7", barrier=False):
    from paddle_tpu import optim
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy
    from paddle_tpu.core import mesh as mesh_lib
    from paddle_tpu.models import resnet50
    from paddle_tpu.nn import costs
    from paddle_tpu.optim.optimizers import apply_updates
    from paddle_tpu.train import Trainer

    if barrier:
        # experiment: stop XLA from fusing BN stat reductions into convs
        from paddle_tpu.models import resnet as resnet_mod

        def barrier_forward(self, x, train=False):
            y = jax.lax.optimization_barrier(self.conv(x))
            return self.act(self.bn(y, train=train))
        resnet_mod.ConvBN.forward = barrier_forward

    trainer = Trainer(
        model=resnet50(num_classes=1000, stem=stem),
        loss_fn=lambda out, b: costs.softmax_cross_entropy(out, b["label"]),
        optimizer=optim.momentum(0.1, 0.9))
    rng = np.random.RandomState(0)
    host_batch = {
        "x": rng.normal(size=(batch_size, 224, 224, 3)).astype(np.float32),
        "label": rng.randint(0, 1000, size=batch_size).astype(np.int32),
    }
    with use_policy(bfloat16_compute):
        trainer.init(jax.random.PRNGKey(0), host_batch)
        trainer._build_train_step()

        model, loss_fn, opt = trainer.model, trainer.loss_fn, trainer.optimizer
        mesh = trainer.mesh

        def one_step(carry, batch, rng):
            params, state, opt_state, step = carry
            rngs = {"dropout": jax.random.fold_in(rng, step)}

            def compute_loss(p):
                out, new = model.apply({"params": p, "state": state},
                                       batch["x"], train=True,
                                       mutable=("state",), rngs=rngs)
                return jnp.mean(loss_fn(out, batch)), new["state"]

            (loss, new_state), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params)
            updates, new_opt = opt.update(grads, opt_state, params, step)
            return (apply_updates(params, updates), new_state, new_opt,
                    step + 1), loss

        def multi(carry, batch, rng, k):
            def body(i, c_l):
                c, _ = c_l
                return one_step(c, batch, rng)
            return jax.lax.fori_loop(0, k, body, (carry, jnp.zeros(())))

        repl = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
        multi_jit = jax.jit(
            multi,
            in_shardings=((repl,) * 4, data, repl),
            static_argnums=(3,), donate_argnums=(0,))
    return trainer, host_batch, multi_jit


def main():
    import argparse
    from paddle_tpu.core.dtypes import bfloat16_compute, use_policy

    ap = argparse.ArgumentParser()
    ap.add_argument("--stem", default="conv7")
    ap.add_argument("--barrier", action="store_true")
    ap.add_argument("--batches", default="128")
    args = ap.parse_args()

    out = {"stem": args.stem, "barrier": args.barrier}
    for bs in [int(b) for b in args.batches.split(",")]:
        trainer, host_batch, multi_jit = build(bs, stem=args.stem,
                                               barrier=args.barrier)
        ts = trainer.train_state
        batch = trainer._shard(host_batch)
        key = jax.random.PRNGKey(1)

        with use_policy(bfloat16_compute):
            # --- per-call ----------------------------------------------------
            p, st, os_, step = ts.params, ts.state, ts.opt_state, ts.step
            for _ in range(3):
                p, st, os_, step, loss, _ = trainer._train_step(
                    p, st, os_, step, batch, key)
            float(loss)
            t0 = time.perf_counter()
            for _ in range(20):
                p, st, os_, step, loss, _ = trainer._train_step(
                    p, st, os_, step, batch, key)
            float(loss)
            ms1 = (time.perf_counter() - t0) / 20 * 1e3
            out[f"bs{bs}_per_call_ms"] = round(ms1, 2)
            print("partial:", json.dumps(out), flush=True)

            # --- fori_loop k=10 ---------------------------------------------
            carry = (p, st, os_, step)
            k = 10
            carry, loss = multi_jit(carry, batch, key, k)   # compile+warm
            float(loss)
            t0 = time.perf_counter()
            for _ in range(4):
                carry, loss = multi_jit(carry, batch, key, k)
            float(loss)
            ms2 = (time.perf_counter() - t0) / (4 * k) * 1e3
            out[f"bs{bs}_fori10_ms"] = round(ms2, 2)
            out[f"bs{bs}_img_s_fori"] = round(bs / ms2 * 1e3, 1)
            out[f"bs{bs}_mfu_fori"] = round(
                bs / ms2 * 1e3 * 4.089e9 * 6 / 197e12 * 100, 1)
            print("partial:", json.dumps(out), flush=True)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
