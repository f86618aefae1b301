"""Config-driven training CLI — the ``paddle_trainer --config=...`` analog
(reference: ``trainer/TrainerMain.cpp:17`` + ``utils/Flags.cpp``: the v1
workflow where a run is fully described by config files, no user code).

    python -m paddle_tpu.train.cli --model_config model.json \
        --dataset mnist --optimizer adam --num_passes 3 --batch_size 64

The model config is the serialized model IR (``core/config.py`` — produce it
with ``paddle_tpu.inference.dump_config`` or an exported model directory);
datasets resolve from ``paddle_tpu.data.datasets`` by name; everything else
is :class:`~paddle_tpu.utils.flags.TrainerFlags`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import jax

from paddle_tpu import data
from paddle_tpu.core.config import build_module, config_from_json
from paddle_tpu.data import datasets as dataset_lib
from paddle_tpu.nn import costs
from paddle_tpu.train import Trainer
from paddle_tpu.train.evaluators import ClassificationError
from paddle_tpu.utils.flags import TrainerFlags, parse_flags

__all__ = ["TrainCliFlags", "run", "main"]


@dataclasses.dataclass
class TrainCliFlags(TrainerFlags):
    model_config: str = ""           # IR json file, or an export()ed dir
    config: str = ""                 # v1-style DSL config SCRIPT (.py)
    dataset: str = "mnist"           # name in paddle_tpu.data.datasets
    optimizer: str = "adam"          # name in paddle_tpu.optim
    loss: str = "softmax_ce"         # softmax_ce | mse
    trusted_config: bool = False     # allow non-registry classes in the IR
    # job: train | test | checkgrad | time — the reference trainer's --job
    # modes (TrainerMain.cpp:25: train / test / checkgrad; TrainerBenchmark
    # --job=time)
    job: str = "train"
    time_batches: int = 10           # batches timed by --job time


def _load_model(path: str, trusted: bool):
    if os.path.isdir(path):          # an export()/merge_model() directory
        path = os.path.join(path, "model.json")
    with open(path) as f:
        return build_module(config_from_json(f.read()), trusted=trusted)


def _make_reader(name: str, batch_size: int, split: str = "train"):
    maker = getattr(dataset_lib, name)
    raw = maker(split)
    sample = next(iter(raw()))
    if isinstance(sample, tuple) and len(sample) == 2:
        r = data.map_readers(lambda s: {"x": s[0], "label": s[1]}, raw)
    else:
        raise SystemExit(
            f"dataset {name!r} yields {type(sample)}; the CLI drives "
            f"(input, label) datasets — write a custom loop for others")
    return data.batched(r, batch_size)


def _make_optimizer(name: str, lr: float):
    from paddle_tpu import optim
    maker = getattr(optim, name, None)
    if maker is None:
        raise SystemExit(f"unknown optimizer {name!r}")
    return maker(lr)


def _make_loss(name: str):
    if name == "softmax_ce":
        return lambda out, b: costs.softmax_cross_entropy(out, b["label"])
    if name == "mse":
        return lambda out, b: costs.mse(out, b["label"])
    raise SystemExit(f"unknown loss {name!r}")


def _make_evaluator(name):
    from paddle_tpu.train import evaluators as ev
    table = {"classification_error": ev.ClassificationError,
             "auc": ev.Auc, "chunk": ev.ChunkEvaluator}
    if name in (None, "", "none"):
        return None
    if name not in table:
        raise SystemExit(f"unknown evaluator {name!r}")
    return table[name]()


def run_config_script(flags: TrainCliFlags) -> dict:
    """Execute a v1-style DSL config SCRIPT and train it — the
    ``paddle_trainer --config=trainer_config.py`` workflow (reference:
    ``TrainerMain.cpp:17`` embedding CPython to run ``parse_config``).

    The script (see ``configs/``) uses ``paddle_tpu.config_helpers``:
    ``settings(...)`` for run knobs, the layer DSL for the model, and
    ``outputs(cost_node)``; it defines ``train_reader`` (and optionally
    ``test_reader``) callables yielding dict batches keyed by data-layer
    names — the ``@provider`` analog living next to the config, exactly as
    the reference paired config scripts with dataprovider scripts.
    """
    import contextlib

    from paddle_tpu import config_helpers as H
    from paddle_tpu.core import dtypes

    ns = {"__name__": "__paddle_tpu_config__",
          "__file__": os.path.abspath(flags.config)}
    with open(flags.config) as f:
        code = compile(f.read(), flags.config, "exec")
    H.get_run_config(reset=True)       # drop any stale state
    exec(code, ns)                     # the config IS a program (v1 semantics)
    cfg = H.get_run_config(reset=True)
    if cfg.network is None:
        raise SystemExit(f"{flags.config} never called outputs(...)")
    if "train_reader" not in ns:
        raise SystemExit(f"{flags.config} must define train_reader()")
    net = cfg.network
    s = cfg.settings
    input_names = [n for n in net.data_names if n is not None]

    # Precedence: an explicitly-passed flag (CLI/env/json) beats the
    # script's settings(); otherwise the script wins over the flag default
    # (the reference's gflags-beat-config ordering, utils/Flags.cpp).
    explicit = getattr(flags, "_explicit", frozenset())

    def pick(key, flag_val):
        if key in explicit:
            return flag_val
        return s.get(key, flag_val)

    def net_forward(model, variables, batch, train, rngs):
        args = [batch[n] for n in input_names]
        if train:
            out, new = model.apply(variables, *args, train=True,
                                   mutable=("state",), rngs=rngs)
            return out, new.get("state", {})
        return model.apply(variables, *args), variables.get("state", {})

    batch_size = int(pick("batch_size", flags.batch_size))
    reader = ns["train_reader"](batch_size)

    # Output contract (v1 `Outputs(...)`): outputs[0] is the per-example
    # cost; an optional second output (e.g. logits) feeds the evaluator —
    # the role of the reference's evaluator layers attached to specific
    # layer outputs.
    def script_loss(out, b):
        return out[0] if isinstance(out, tuple) else out

    evaluator = _make_evaluator(s.get("evaluator"))
    if evaluator is not None:
        inner = evaluator

        class _SecondOutput:
            def reset(self):
                inner.reset()

            def batch_stats(self, out, batch):
                o = out[1] if isinstance(out, tuple) else out
                return inner.batch_stats(o, batch)

            def update(self, stats):
                inner.update(stats)

            def result(self):
                return inner.result()

        evaluator = _SecondOutput()

    trainer = Trainer(
        model=net,
        loss_fn=script_loss,           # cost layers return per-example costs
        optimizer=_make_optimizer(
            pick("optimizer", flags.optimizer),
            float(pick("learning_rate", flags.learning_rate))),
        forward=net_forward,
        evaluator=evaluator,
        nan_check=flags.nan_check,
        param_stats_period=flags.param_stats_period or None)
    last = {}

    def handler(e):
        from paddle_tpu.train import events as ev
        if isinstance(e, ev.EndPass):
            last.update(e.metrics)

    policy = (dtypes.use_policy(dtypes.bfloat16_compute)
              if flags.use_bf16 else contextlib.nullcontext())
    num_passes = int(pick("num_passes", flags.num_passes))
    test_reader = (ns["test_reader"](batch_size)
                   if "test_reader" in ns else None)
    with policy:
        trainer.init(jax.random.PRNGKey(flags.seed), next(iter(reader())))
        if flags.job != "train":
            return _run_alt_job(flags, trainer, reader, test_reader)
        trainer.train(
            reader, num_passes=num_passes, event_handler=handler,
            checkpoint_dir=flags.checkpoint_dir or None,
            checkpoint_keep=flags.checkpoint_keep,
            saving_period=flags.saving_period or None,
            log_period=flags.log_period, resume=flags.resume)
    return last


def _run_alt_job(flags: TrainCliFlags, trainer: Trainer, reader,
                 test_reader=None) -> dict:
    """The reference trainer's non-train --job modes
    (``TrainerMain.cpp:25``: test / checkgrad; ``TrainerBenchmark.cpp``:
    --job=time). Shared by the IR and config-script paths (trainer already
    initialized)."""
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    if flags.job == "test":
        # load latest checkpoint if available, evaluate the test stream
        if flags.checkpoint_dir:
            from . import checkpoint as ckpt_mod
            if ckpt_mod.latest_pass(flags.checkpoint_dir) is not None:
                trainer.restore(flags.checkpoint_dir)
        cost, metrics = trainer.evaluate(test_reader or reader)
        return {"test_cost": cost, **{f"test_{k}": v
                                      for k, v in metrics.items()}}

    if flags.job == "checkgrad":
        # whole-model numeric gradient check on one batch
        # (Trainer::checkGradient, --job=checkgrad)
        from paddle_tpu.utils.gradcheck import check_gradients
        batch = jax.tree_util.tree_map(jnp.asarray, next(iter(reader())))
        state = trainer.train_state.state
        fwd = trainer._forward
        loss_fn = trainer.loss_fn
        model = trainer.model

        def loss_of(p):
            out, _ = fwd(model, {"params": p, "state": state}, batch, True,
                         {"dropout": jax.random.PRNGKey(0)})
            return jnp.mean(loss_fn(out, batch))

        # smoke-level whole-model check (rigorous per-layer checks live in
        # tests/): f32 central differences over a full model need headroom
        worst = check_gradients(loss_of, trainer.train_state.params,
                                num_directions=3, rtol=6e-2)
        return {"checkgrad_worst_rel_err": float(worst), "checkgrad_ok": 1}

    if flags.job == "time":
        # --job=time: ms/batch over time_batches (TrainerBenchmark.cpp)
        trainer._build_train_step()
        ts = trainer.train_state
        batches = []
        for i, b in enumerate(reader()):
            if i >= flags.time_batches:
                break
            batches.append(trainer._shard(b))
        if not batches:
            raise SystemExit("--job time: reader yielded no batches")
        params, state, opt_state, step = (ts.params, ts.state, ts.opt_state,
                                          ts.step)
        key = jax.random.PRNGKey(1)
        # warmup (compile)
        params, state, opt_state, step, loss, _ = trainer._train_step(
            params, state, opt_state, step, batches[0], key)
        float(np.asarray(jax.device_get(loss)))
        t0 = _time.perf_counter()
        for b in batches:
            params, state, opt_state, step, loss, _ = trainer._train_step(
                params, state, opt_state, step, b, key)
        float(np.asarray(jax.device_get(loss)))
        ms = (_time.perf_counter() - t0) / len(batches) * 1e3
        return {"ms_per_batch": ms, "batches": len(batches)}

    raise SystemExit(f"unknown --job {flags.job!r} "
                     "(train | test | checkgrad | time)")


def run(flags: TrainCliFlags) -> dict:
    """Build everything from config and train; returns final pass metrics."""
    import contextlib

    from paddle_tpu.core import dtypes

    if flags.config:
        return run_config_script(flags)
    if not flags.model_config:
        raise SystemExit("--model_config or --config is required")
    model = _load_model(flags.model_config, flags.trusted_config)
    reader = _make_reader(flags.dataset, flags.batch_size)
    trainer = Trainer(
        model=model,
        loss_fn=_make_loss(flags.loss),
        optimizer=_make_optimizer(flags.optimizer, flags.learning_rate),
        evaluator=ClassificationError() if flags.loss == "softmax_ce"
        else None,
        nan_check=flags.nan_check,
        param_stats_period=flags.param_stats_period or None)
    last = {}

    def handler(e):
        from paddle_tpu.train import events as ev
        if isinstance(e, ev.EndPass):
            last.update(e.metrics)

    policy = (dtypes.use_policy(dtypes.bfloat16_compute)
              if flags.use_bf16 else contextlib.nullcontext())
    with policy:
        trainer.init(jax.random.PRNGKey(flags.seed), next(iter(reader())))
        if flags.job != "train":
            test_reader = _make_reader(flags.dataset, flags.batch_size,
                                       split="test")
            return _run_alt_job(flags, trainer, reader, test_reader)
        trainer.train(
            reader, num_passes=flags.num_passes, event_handler=handler,
            checkpoint_dir=flags.checkpoint_dir or None,
            checkpoint_keep=flags.checkpoint_keep,
            saving_period=flags.saving_period or None,
            log_period=flags.log_period, resume=flags.resume)
    return last


def main(argv: Optional[list] = None) -> None:
    flags = parse_flags(TrainCliFlags, argv)
    from ..obs import xla_cache
    xla_cache.setup()               # before the first compile
    metrics = run(flags)
    print(json.dumps({k: (round(v, 6) if isinstance(v, float) else v)
                      for k, v in metrics.items()}))


if __name__ == "__main__":
    main()
