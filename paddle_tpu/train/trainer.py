"""The training driver — successor of paddle/trainer + the v2 SGD event loop.

Reference call stack (SURVEY.md §3.1/§3.2): ``Trainer::train`` → ``trainOnePass``
→ ``TrainerInternal::trainOneBatch`` (forwardBackward; updater; evaluators;
events), with data-parallelism delegated to ``MultiGradientMachine`` threads and
remote updaters talking to parameter servers.

TPU-native design: ONE jit-compiled ``train_step`` closed over model+optimizer,
executed over a device mesh. Data parallelism is a sharding annotation, not a
thread pool: the batch arrives sharded over the ``data`` axis, parameters are
replicated, and XLA inserts the gradient all-reduce (the entire pserver tier of
the reference collapses into this). Evaluator statistics ride in the same
compiled step. The host loop only feeds data, fires events, logs, and
checkpoints — mirroring the v2 ``SGD.train`` surface
(``python/paddle/v2/trainer.py:124``).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

_log = logging.getLogger("paddle_tpu.trainer")

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import mesh as mesh_lib
from ..core.module import Module
from ..obs.trace import live, tspan
from ..optim.optimizers import Optimizer, apply_updates
from ..utils.stats import StatSet
from . import checkpoint as ckpt_lib
from . import events as ev
from .faults import Preempted

__all__ = ["Trainer", "TrainState"]


class _Timed:
    """One timed region of the loop, with ONE clock pair: read once on
    entry and once on exit. The pair goes to the ``StatSet`` row
    (always; ``key`` None = no row), to the live tracer's span and its
    profiler annotation (``span`` is None unless ``obs.trace.live``
    found a tracer), and stays on ``seconds`` for the telemetry
    record. With ``jitted`` (the compiled function the region calls) a
    live span says ``compiled`` when its jit cache grew inside the
    region: which step recompiled."""

    __slots__ = ("_stats", "_key", "span", "_jitted", "_programs", "_t0",
                 "seconds")

    def __init__(self, stats: StatSet, key: Optional[str], span,
                 jitted=None):
        self._stats = stats
        self._key = key
        self.span = span
        self._jitted = jitted if span is not None else None

    def __enter__(self):
        if self.span is not None:
            if self._jitted is not None:
                self._programs = _jit_cache_size(self._jitted)
            self._t0 = self.span.__enter__().t0_ns
        else:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self.span is not None:
            if self._jitted is not None \
                    and _jit_cache_size(self._jitted) > self._programs:
                self.span.set(compiled=True)
            self.span.__exit__(*exc)
            t1 = self.span.t1_ns
        else:
            t1 = time.perf_counter_ns()
        self.seconds = (t1 - self._t0) / 1e9
        if self._key is not None:
            self._stats.add(self._key, self.seconds)
        return False


def _jit_cache_size(fn) -> int:
    """Programs in a jitted function's cache (0 where it cannot say)."""
    size = getattr(fn, "_cache_size", None)
    return int(size()) if size is not None else 0


def _batch_fingerprint(host_batch) -> int:
    """CRC32 of a host batch's raw bytes — recorded in mid-pass checkpoints
    so a resume can detect a nondeterministic reader (a shuffled/buffered
    reader replayed from scratch yields a different batch at the same
    index, silently training on a different remainder otherwise)."""
    import zlib
    crc = 0
    leaves = jax.tree_util.tree_leaves(host_batch)
    for leaf in leaves:
        arr = np.ascontiguousarray(np.asarray(leaf))
        crc = zlib.crc32(arr.tobytes(), crc)
    return crc


def _batch_shapes(host_batch):
    """Leaf shapes of a host batch — fused groups must stack uniformly."""
    return tuple(np.shape(leaf)
                 for leaf in jax.tree_util.tree_leaves(host_batch))


def _step_fingerprint(batch_tree) -> tuple:
    """The trainer's step fingerprint: per-leaf (shape, dtype) of the batch
    pytree feeding one dispatch. A dispatch whose fingerprint was never
    seen before will trace + compile (a jit cache miss); the telemetry
    retrace counter is keyed by exactly this tuple. For fused groups the
    stacked leaves carry [K, M, ...], so K/M changes fingerprint too.

    Metadata-only on purpose: leaves may be DEVICE arrays (the pipelined
    path fingerprints the staged group) and an ``np.asarray`` here would
    download them."""
    def leaf_dtype(leaf):
        dt = getattr(leaf, "dtype", None)
        return str(dt) if dt is not None else str(np.asarray(leaf).dtype)

    return tuple((tuple(np.shape(leaf)), leaf_dtype(leaf))
                 for leaf in jax.tree_util.tree_leaves(batch_tree))


class TrainState:
    """The complete training pytree: params, module state, optimizer state, step."""

    def __init__(self, params, state, opt_state, step):
        self.params = params
        self.state = state
        self.opt_state = opt_state
        self.step = step

    def as_dict(self):
        return {"params": self.params, "state": self.state,
                "opt_state": self.opt_state,
                "step": self.step}


class Trainer:
    """Single-controller training driver.

    Args:
      model: the Module.
      loss_fn: ``(outputs, batch) -> per-example losses`` (reduced by mean,
        masked by ``batch['weight']`` if present).
      optimizer: an ``optim.Optimizer``.
      mesh: device mesh; defaults to all devices on the ``data`` axis.
      forward: optional ``(model, variables, batch, train, rngs) -> (out, new_state)``
        override for models with non-standard inputs (default feeds
        ``batch['x']``).
      evaluator: optional EvaluatorSet/Evaluator whose stats are computed
        inside the compiled step.
      param_sharding: optional model-parallel layout — either a
        ``parallel.ShardingRules`` or a pytree of PartitionSpecs matching the
        params tree. Params are materialized in that layout at init;
        optimizer state inherits each param's layout (eager
        ``optimizer.init`` on committed params — eager zeros_like
        propagates sharding); XLA inserts the collectives. Default fully
        replicated.
      steps_per_call: K > 1 fuses K optimizer steps into ONE device dispatch
        (a donated ``lax.scan`` over K pre-stacked host batches) — amortizes
        the per-call Python->device dispatch. The compiled program returns the
        stacked per-step losses/evaluator stats; host events, logging, and
        ``saving_period`` checkpoints replay per step after each call (so
        BeginIteration/EndIteration both fire post-dispatch, and mid-pass
        saves land on call boundaries). Numerically identical to K plain
        steps (same traced step body).
      grad_accum: M > 1 accumulates gradients over M consecutive host
        batches (microbatches) per optimizer step, in a donated-accumulator
        inner ``lax.scan`` — large effective batches beyond what HBM fits in
        one forward/backward. Loss/grads are the mean over the M microbatch
        means, each microbatch weight-normalized by its own ``weight`` field
        (mean-of-means; mask/weight-correct within each microbatch). The
        optimizer update — and with ``param_sharding`` the gradient
        all-reduce the partitioner hoists out of the accumulation loop —
        fires once per accumulated step, not per microbatch.
      grad_sync: None (default) leaves the data-parallel gradient
        all-reduce to the SPMD partitioner (one implicit collective per
        gradient tensor, typically combined+scheduled by the backend as a
        monolithic post-backward sync). ``"bucketed"`` takes explicit
        ownership (:mod:`paddle_tpu.parallel.overlap`): each microbatch's
        forward+backward runs in a manual-dp ``shard_map`` region (other
        mesh axes stay GSPMD-auto, so tensor-parallel ``param_sharding``
        composes), parameters are partitioned into byte-budgeted buckets
        in reverse layer order, and a ``custom_vjp`` marker all-reduces
        each bucket's cotangents as ONE flat psum the moment that
        bucket's backward slice completes — the scheduler can float each
        bucket's collective under the remaining backward compute. Models
        with a remat scan-over-layers stack sync the per-layer slice
        inside the scan transpose (``TransformerLM.grad_sync_scan_paths``
        protocol). ``"fused"`` is the single-bucket baseline: one flat
        post-backward all-reduce — bit-exact vs bucketed in f32 (same
        elementwise reduction, different granularity). With
        ``grad_accum > 1`` local gradients accumulate across microbatches
        and sync once per optimizer step, never per microbatch. Degrades
        gracefully (one warning, implicit sync) when the mesh has no
        multi-device dp axis or ``param_sharding`` shards params over the
        dp axis. Semantic deltas of the explicit modes: module-state
        updates (BN running stats) happen per dp shard — torch-DDP
        semantics, warned once when state is non-empty — and dropout
        draws per shard from a dp-coordinate-folded key (independent
        masks, but a different sample stream than the implicit path).
        Forward outputs must be batch-led (the ``shard_batch`` contract):
        a non-batch-led output leaf is either rejected at trace time
        (leading dim not divisible by dp) or would be mis-assembled —
        use ``grad_sync=None`` for such models.
      bucket_mb: bucket byte budget in MiB for ``grad_sync="bucketed"``
        (default 4.0). Smaller buckets start syncing earlier but pay more
        per-collective latency; see README "Gradient-sync overlap".
      pipeline_depth: W > 1 turns on the async host pipeline
        (``train/host_pipeline.py``): a background stager thread stacks and
        ``device_put``-shards group N+1 (double-buffered) while call N runs
        on device, and up to W fused calls stay in flight with their host
        replay (events, costs, evaluator updates, logging, telemetry)
        deferred until drained — removing the per-group host staging and
        the eager per-group loss fetch from the critical path. Draining is
        FIFO (the serial event order is preserved exactly), forced at
        every ``saving_period`` checkpoint boundary (saves observe a
        quiesced ``train_state``; ``nan_check``'s skip-the-poisoned-save
        rule still holds) and at pass end. Bit-identical math to the
        serial loop — same dispatches, same order, same rng. The plain
        (K=1, M=1) loop gets the same deferred-fetch treatment when
        ``nan_check`` is off (with it on, plain mode must trap each loss
        before the next dispatch, so it stays serial). The default W=1 is
        today's serial loop, byte-identical. With telemetry attached,
        pipelined calls skip the per-call device fence (it would serialize
        the pipeline) and record ``stage_ms`` / ``drain_wait_ms`` /
        ``overlap_frac`` instead. CONTRACT CHANGE for event handlers that
        read ``trainer.train_state``: at replay time the state may already
        include up to W later dispatched groups (only ``saving_period``
        saves are quiesced, via the forced boundary drain) — handlers
        that snapshot state per iteration need ``pipeline_depth=1``.
      telemetry: optional :class:`paddle_tpu.obs.Telemetry`. When attached,
        the trainer records a per-call step-time breakdown (host stack /
        shard / dispatch / fenced device / events-replay), tracks jit
        retraces by step fingerprint (with per-compile wall time and an
        HLO cost-analysis FLOPs estimate feeding MFU/tokens-per-sec),
        samples device memory, and — when ``telemetry.health`` — traces
        the training-health scalars (grad/param/update norms, NaN
        sentinel) INTO the compiled step, returned alongside the losses.
        With ``telemetry=None`` (default) the hot loop is unchanged: same
        traced step function, same dispatch count, same donation, and
        zero extra device fetches or fences.
      tracer: optional :class:`paddle_tpu.obs.Tracer`. When attached, the
        trainer records thread-aware timeline spans (plan / stack /
        device_put / dispatch / fence / drain-wait / events-replay /
        checkpoint-save / eval on the main thread; stack + shard on the
        stager thread, flow-linked to the later dispatch and drain) and
        serializes them as Chrome Trace Event JSON
        (``tracer.save(path)`` — open in Perfetto), so host/device
        overlap is visually auditable. Spans are host-side wall clocks
        only: no extra dispatch, no fence. With ``tracer=None`` the same
        spans are live while a ``jax.profiler`` session is active in the
        process (they go to ``obs.trace.session_tracer()`` and into the
        profiler's trace as ``paddle_tpu:<name>``), and otherwise the hot
        loop does the same work and dispatches (pinned by
        tests/test_trace.py alongside tests/test_obs.py's telemetry-off
        invariant). The plain loop adds ``train_step`` / ``reader_wait`` /
        ``loss_fetch`` / ``events``.
      anomaly: optional :class:`paddle_tpu.obs.AnomalyDetector`. Consumes
        every telemetry step record (requires ``telemetry``); on a
        detected anomaly (slow-step outlier, retrace burst, drain stall,
        memory high-water, NaN sentinel) it dumps a one-shot forensics
        bundle — telemetry ring + recent trace spans + config/env/mesh
        snapshot + verdict — and can arm a ``jax.profiler`` capture for
        the next fused call. Observation only: training continues, and a
        detector failure is logged, never raised.
      faults: optional :class:`paddle_tpu.train.faults.FaultSchedule` —
        the deterministic fault-injection plane (ISSUE 10). When
        attached, the named injection points fire at their scheduled
        step/save/group: ``crash_at_step``/``preempt_at_step`` after
        that optimizer step's host replay, the save-path points inside
        the checkpoint writer (sync or async), and
        ``stager_error_at_group`` in the host-pipeline stager thread.
        With ``faults=None`` (default) the hot loop is the exact
        pre-faults build: same traced step, dispatch count, donation,
        zero extra fences (pinned by tests/test_resilience.py).

    Preemption: ``request_stop(reason)`` (typically from a SIGTERM/SIGINT
    handler — see :func:`paddle_tpu.train.resilience.
    install_preemption_handler`) asks ``train()`` to stop gracefully at
    the NEXT GROUP BOUNDARY: the host pipeline and deferred-fetch window
    are drained, a final quiesced checkpoint (with the data-iterator
    position) is written through the active save path, the async
    checkpointer is fenced, and ``train()`` raises
    :class:`~paddle_tpu.train.faults.Preempted` — a distinct CLEAN
    status the resilience supervisor returns instead of retrying.
    """

    def __init__(self, model: Module, loss_fn: Callable, optimizer: Optimizer,
                 mesh=None, forward: Optional[Callable] = None,
                 evaluator=None, param_sharding=None, donate: bool = True,
                 nan_check: bool = False,
                 param_stats_period: Optional[int] = None,
                 steps_per_call: int = 1, grad_accum: int = 1,
                 grad_sync: Optional[str] = None, bucket_mb: float = 4.0,
                 pipeline_depth: int = 1, telemetry=None, tracer=None,
                 anomaly=None, faults=None, metrics=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh or mesh_lib.default_mesh()
        self.evaluator = evaluator
        self.stats = StatSet("trainer")
        self._forward = forward or self._default_forward
        self._param_sharding = param_sharding
        self._param_specs = None
        self._train_step = None
        self._eval_step = None
        self._donate = donate
        # nan_check: host-side finiteness trap on the per-step loss (the
        # reference's feenableexcept analog, TrainerMain.cpp:36); on trip it
        # names the non-finite param/state leaves before raising.
        self._nan_check = nan_check
        # param_stats_period: per-param scale telemetry every N batches (the
        # reference's --show_parameter_stats_period, TrainerInternal.cpp:81).
        self._param_stats_period = param_stats_period
        if steps_per_call < 1 or grad_accum < 1:
            raise ValueError("steps_per_call and grad_accum must be >= 1")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self.steps_per_call = int(steps_per_call)
        self.grad_accum = int(grad_accum)
        # grad_sync: explicit dp gradient synchronization (bucketed
        # overlap / fused baseline) — validated eagerly, resolved against
        # the mesh lazily at step build (parallel.overlap).
        from ..parallel import overlap as overlap_lib
        if grad_sync not in overlap_lib.GRAD_SYNC_MODES:
            raise ValueError(
                f"grad_sync must be one of "
                f"{overlap_lib.GRAD_SYNC_MODES}, got {grad_sync!r}")
        if bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
        self.grad_sync = grad_sync
        self.bucket_mb = float(bucket_mb)
        self._grad_sync_warned = False
        self._state_sync_warned = False
        # pipeline_depth: bounded in-flight dispatch window (1 = serial).
        self.pipeline_depth = int(pipeline_depth)
        # host-side optimizer-step mirror: lets the fused replay number its
        # steps without fetching the device step scalar (a sync that would
        # defeat the pipeline); re-anchored from train_state at pass start.
        self._host_step = 0
        # telemetry: None = the untelemetered hot loop, byte-identical to
        # the pre-obs build (no health outputs in the traced step, no
        # fencing, no extra fetches — pinned by tests/test_obs.py).
        self.telemetry = telemetry
        # tracer/anomaly: same rule — with None (and no jax.profiler
        # session, see obs.trace.live) a span site is a shared no-op
        # context; anomaly observation only ever follows a telemetry
        # emit, which telemetry=None already gates.
        self.tracer = tracer
        if anomaly is not None and telemetry is None:
            raise ValueError(
                "AnomalyDetector consumes telemetry step records — pass "
                "telemetry=Telemetry(...) alongside anomaly=")
        self.anomaly = anomaly
        # metrics: optional MetricsHub / scoped view (ISSUE 19) — the
        # trainer publishes a step-time histogram and a tokens/sec
        # gauge from each finalized step record. Same None doctrine as
        # telemetry/tracer: off means the hot loop is untouched.
        self.metrics = metrics
        # faults: None = the exact pre-faults hot loop (every injection
        # point is behind a host-side `is not None` check — no traced-step
        # or dispatch-count change; pinned by tests/test_resilience.py).
        self.faults = faults
        # graceful-stop request (SIGTERM handler / injected preemption /
        # request_stop()); a bare attribute write, so it is safe from
        # signal handlers and other threads. Consumed at group boundaries.
        self._stop_requested: Optional[str] = None
        self._fused_step = None
        self.train_state: Optional[TrainState] = None
        self._last_iter_state: Optional[Dict[str, Any]] = None
        # fallback-chain bookkeeping from the last restore (ISSUE 10)
        self.last_quarantined: list = []
        self._last_restored_pass: Optional[int] = None

    def _health_on(self) -> bool:
        return self.telemetry is not None and self.telemetry.health

    # -- preemption + fault injection (ISSUE 10) -----------------------------

    def request_stop(self, reason: str = "requested") -> None:
        """Ask the training loop to stop gracefully at the next group
        boundary (drain the pipeline, write a quiesced checkpoint, raise
        :class:`~paddle_tpu.train.faults.Preempted`). Safe to call from a
        signal handler or another thread — it only writes an attribute;
        all the work happens on the training thread at the boundary."""
        if self._stop_requested is None:
            self._stop_requested = reason
            _log.warning("graceful stop requested (%s): will quiesce at "
                         "the next group boundary", reason)

    def _fire_step_faults(self, step: int) -> None:
        """One optimizer step's host replay just finished: fire any
        scheduled crash (raises) or preemption (requests a graceful
        stop) keyed to that step. Callers gate on ``faults is not
        None``, so the off path never even makes this call."""
        fs = self.faults
        fs.maybe_crash_step(step)
        if fs.should_preempt(step):
            self.request_stop(f"injected preemption at step {step}")

    def _maybe_stop(self, pipe, pending, pass_id, next_batch, handler,
                    costs, log_period, checkpoint_dir, checkpoint_keep,
                    save_fn, last_batch=None) -> None:
        """Group-boundary graceful-stop check. When a stop is pending:
        drain the in-flight window (pipelined fused / deferred plain) so
        ``train_state`` quiesces at exactly ``next_batch`` consumed
        batches, write a final mid-pass checkpoint carrying the iterator
        position — with the last consumed batch's fingerprint
        (``last_batch``), so the resume-time nondeterministic-reader
        check guards the preempt path like every other mid-pass save —
        and exit via :class:`Preempted`. The async checkpointer (when
        active) is fenced by ``train()``'s finally — the preempt save is
        on disk before ``train()`` unwinds."""
        if self._stop_requested is None:
            return
        reason = self._stop_requested
        if pipe is not None:
            pipe.flush()           # FIFO drain: replay order preserved
        while pending:
            self._replay_plain(pending.pop(0), pass_id, handler, costs,
                               log_period, checkpoint_dir, checkpoint_keep,
                               save_fn)
        if checkpoint_dir:
            it = {"pass": pass_id, "next_batch": next_batch,
                  "completed": 0, "preempted": 1}
            if last_batch is not None:
                it["batch_crc"] = _batch_fingerprint(last_batch)
            with tspan(self.tracer, "checkpoint_save",
                       preempt_next_batch=next_batch):
                save_fn(
                    checkpoint_dir, pass_id,
                    {**self.train_state.as_dict(), "iter": it},
                    keep_last=checkpoint_keep)
        raise Preempted(pass_id=pass_id, next_batch=next_batch,
                        reason=reason)

    # -- anomaly plumbing ----------------------------------------------------

    def _anomaly_observe(self, rec) -> None:
        """Feed one finalized telemetry record to the anomaly detector.
        Detection is observation: a detector crash must never kill the
        run it watches, so failures log and training continues. Verdicts
        are echoed into the telemetry stream as ``kind="anomaly"``
        records (ISSUE 6: the run's JSONL is self-contained — the report
        CLI counts anomalies without reading bundle directories). The
        metrics registry (ISSUE 19) feeds from the same finalized
        records — every emit_step site already flows through here."""
        if (self.metrics is not None and rec is not None
                and rec.get("kind") == "step"):
            m = self.metrics
            k = rec.get("k_steps") or 1
            m.counter("train_steps", "optimizer steps completed").inc(k)
            total_ms = ((rec.get("device_ms") or 0.0)
                        + (rec.get("dispatch_ms") or 0.0))
            if total_ms > 0:
                m.histogram("train_step_ms",
                            "per-step wall (device+dispatch) ms"
                            ).observe(total_ms / k)
            if rec.get("tokens_per_sec") is not None:
                m.gauge("train_tokens_per_sec",
                        "training token throughput"
                        ).set(rec["tokens_per_sec"])
            if rec.get("loss") is not None:
                m.gauge("train_loss", "last step loss").set(rec["loss"])
        if self.anomaly is None or rec is None:
            return
        try:
            verdicts = self.anomaly.observe(rec)
        except Exception:
            _log.exception("anomaly detector failed (training continues)")
            return
        if verdicts and self.telemetry is not None:
            for v in verdicts:
                try:
                    vd = v.to_dict()
                    vd["anomaly_kind"] = vd.pop("kind")
                    self.telemetry.emit_event({"kind": "anomaly", **vd})
                except Exception:
                    _log.exception("anomaly telemetry emit failed")

    def _maybe_profiled_call(self, fn, *args):
        """Run ONE compiled dispatch, wrapped in an anomaly-armed
        ``jax.profiler`` capture when one is pending (every dispatch path
        — fused, serial plain, deferred plain — polls here, so
        ``arm_profiler`` is never a silent no-op). Returns ``(out,
        profiled)``; a profiled call fences inside the capture so the
        device compute lands in it — its record is stamped ``profiled``
        and excluded from rates/wall statistics."""
        prof_dir = (self.anomaly.take_profiler_request()
                    if self.anomaly is not None else None)
        if prof_dir is None:
            return fn(*args), False
        from ..obs.trace import jax_profile
        with jax_profile(prof_dir):
            out = fn(*args)
            jax.block_until_ready(out[:5])   # capture the compute,
        return out, True                     # not just the enqueue

    def _timed(self, stat_key: Optional[str], span_name: str,
               jitted=None, **facts) -> _Timed:
        """A timed region: the ``StatSet`` row ``stat_key`` and, where a
        tracer is live, the span ``span_name`` with ``facts``. A region
        that calls a compiled step is opened where the call is made and
        not in a helper of its own: every Python frame between the entry
        point and the jit call costs the step's lowering seconds (4 to 5
        s a frame for the 590M step on the v5e host, PERF.md, PR 25)."""
        tracer = live(self.tracer)
        return _Timed(self.stats, stat_key,
                      None if tracer is None
                      else tracer.span(span_name, **facts), jitted)

    def _anomaly_context(self) -> Dict[str, Any]:
        """The config/env/mesh snapshot frozen into a forensics bundle."""
        import os
        mesh = self.mesh
        return {
            "model": type(self.model).__name__,
            "optimizer": type(self.optimizer).__name__,
            "steps_per_call": self.steps_per_call,
            "grad_accum": self.grad_accum,
            "grad_sync": self.grad_sync,
            "pipeline_depth": self.pipeline_depth,
            "donate": self._donate,
            "nan_check": self._nan_check,
            "param_sharding": self._param_sharding is not None,
            "host_step": self._host_step,
            "mesh_axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "device_count": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "env": {k: v for k, v in os.environ.items()
                    if k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))},
        }

    # -- setup ---------------------------------------------------------------

    @staticmethod
    def _default_forward(model, variables, batch, train, rngs):
        if train:
            out, new = model.apply(variables, batch["x"], train=True,
                                   mutable=("state",), rngs=rngs)
            return out, new["state"]
        return model.apply(variables, batch["x"]), variables["state"]

    def init(self, rng, sample_batch: Dict[str, Any]) -> TrainState:
        """Initialize params/state/optimizer from one (host) batch. Models with
        non-standard inputs (custom ``forward=`` arg) implement
        ``init_variables(rng, batch)``."""
        with tspan(self.tracer, "trainer_init"):
            batch = jax.tree_util.tree_map(jnp.asarray, sample_batch)
            if self._param_sharding is not None:
                from ..parallel import sharding as shard_lib
                if hasattr(self.model, "init_variables"):
                    variables = self.model.init_variables(rng, batch)
                    specs = self._param_sharding
                    if isinstance(specs, shard_lib.ShardingRules):
                        specs = specs(variables["params"])
                    params = shard_lib.shard_tree(
                        self.mesh, variables["params"], specs)
                    state = shard_lib.shard_tree(self.mesh,
                                                 variables.get("state", {}))
                else:
                    # Materialize params directly in their sharded
                    # layout — no full replicated copy on one device first.
                    variables, specs = shard_lib.sharded_init(
                        self.model, rng, batch["x"], mesh=self.mesh,
                        rules=self._param_sharding, train=True)
                    params = variables["params"]
                    state = variables.get("state", {})
                self._param_specs = specs
            else:
                if hasattr(self.model, "init_variables"):
                    variables = self.model.init_variables(rng, batch)
                else:
                    variables = self.model.init(rng, batch["x"], train=True)
                params = variables["params"]
                state = variables.get("state", {})
            # Param-shaped optimizer slots inherit each param's committed
            # layout: eager zeros_like/ops on sharded arrays propagate
            # sharding (under jit they would be value-independent constants
            # and land on one device).
            opt_state = self.optimizer.init(params)
            self.train_state = TrainState(*self._commit(
                (params, state, opt_state, jnp.zeros((), jnp.int32))))
            return self.train_state

    def _commit(self, tree):
        """Place every leaf not yet laid out on the trainer's mesh
        replicated on it — the placement the compiled step returns its
        state in. jax keys its trace cache on placement, so a state that
        enters the first step uncommitted and comes back on the mesh
        compiles the step twice."""
        repl = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda x: x if isinstance(getattr(x, "sharding", None),
                                      NamedSharding)
            else jax.device_put(x, repl), tree)

    # -- explicit gradient sync (ISSUE 8) ------------------------------------

    def _resolve_grad_sync(self) -> Optional[str]:
        """Resolve the requested ``grad_sync`` mode against the mesh and
        committed param layout; on degrade, warn ONCE and return None
        (the implicit partitioner sync — never crash a config that
        trains fine without the overlap)."""
        from ..parallel import overlap as overlap_lib
        mode, reason = overlap_lib.resolve_grad_sync(
            self.grad_sync, self.mesh, mesh_lib.DATA_AXIS,
            self._param_specs)
        if self.grad_sync is not None and mode is None and \
                not self._grad_sync_warned:
            self._grad_sync_warned = True
            _log.warning(
                "grad_sync=%r requested but cannot engage: %s — degrading "
                "to the implicit partitioner gradient sync (no-op marker)",
                self.grad_sync, reason)
        return mode

    def _make_synced_grads(self, mode: str):
        """Build the explicit-sync gradient path for one microbatch
        (:mod:`paddle_tpu.parallel.overlap`): the forward+backward runs in
        a ``shard_map`` manual over the dp axis (all other mesh axes stay
        GSPMD-auto, so tensor-parallel ``param_sharding`` composes), each
        device differentiates its LOCAL loss sum, and the only dp
        gradient communication is ours — one flat psum per bucket,
        anchored in the backward by the ``sync_tangent`` markers.

        Returns ``(grads_fn, accum_sync)``:

        - ``grads_fn(params, state, mb, rngs, sync_now)`` matches
          ``microbatch_grads``' return contract
          ``((loss, (new_state, out)), grads)``. ``sync_now=True`` (the
          ``M == 1`` path) applies the bucket markers — grads leave the
          region globally reduced, with each bucket's all-reduce placed
          as-you-go inside the backward. ``sync_now=False`` (the
          accumulation path) returns LOCAL per-device grads.
        - ``accum_sync(grads)`` bucket-syncs an accumulated local
          gradient tree — called once per optimizer step after the
          microbatch scan, never per microbatch.

        The loss is the same weight-normalized global mean as the
        implicit path: local (weighted) sums are psum'd and divided by
        the global weight/count, and the gradient is post-scaled by the
        same denominator — mathematically the mean's gradient, with
        bucketed-vs-fused bit-exactness guaranteed by construction (the
        two modes differ only in all-reduce granularity, and all-reduce
        is an elementwise sum)."""
        from ..parallel import overlap as overlap_lib
        assert self.train_state is not None, "call init() first"
        mesh = self.mesh
        axis = mesh_lib.DATA_AXIS
        model, loss_fn, forward = self.model, self.loss_fn, self._forward
        params0 = self.train_state.params
        if jax.tree_util.tree_leaves(self.train_state.state) and \
                not self._state_sync_warned:
            self._state_sync_warned = True
            _log.warning(
                "grad_sync=%r runs the forward per dp shard: module-state "
                "updates (e.g. BN running stats) use each device's LOCAL "
                "batch statistics (torch-DDP semantics), not global-batch "
                "statistics", mode)
        # The in-scan protocol: a model may declare param paths it syncs
        # per-layer inside its scan-over-layers stack (the remat'd
        # transformer) — those leaves leave the top-level buckets, and the
        # scan hook engages only on the sync-now path (accumulation syncs
        # once per step, so in-scan per-microbatch psums must stay off).
        scan_paths: tuple = ()
        if mode == "bucketed":
            hook = getattr(model, "grad_sync_scan_paths", None)
            if callable(hook):
                scan_paths = tuple(hook() or ())
        # "fused" = one bucket (per dtype — flat buffers cannot mix)
        budget = self.bucket_mb if mode == "bucketed" else 1e9
        buckets_now = overlap_lib.partition_buckets(
            params0, budget, exclude=scan_paths)
        buckets_accum = overlap_lib.partition_buckets(params0, budget)
        in_scan = bool(scan_paths)

        def _sm(fn, in_specs, out_specs):
            # manual over dp only (other mesh axes stay GSPMD-auto); no
            # vma check: per-device grad sums are deliberately
            # device-varying until the bucket marker psums them
            return jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                axis_names=frozenset({axis}), check_vma=False)

        def batch_spec(x):
            return P() if np.ndim(x) == 0 else P(mesh_lib.DATA_AXIS)

        repl_of = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)

        def grads_fn(params, state, mb, rngs, sync_now: bool):
            weighted = mb.get("weight") is not None

            def per_device(params, state, mb, rngs):
                # per-shard rng: fold the dp coordinate into every key so
                # shards draw INDEPENDENT dropout masks (a replicated key
                # would give every shard the same mask over its local
                # batch — an undisclosed 1/dp cut in mask diversity)
                rngs = jax.tree_util.tree_map(
                    lambda k: jax.random.fold_in(k, lax.axis_index(axis)),
                    rngs)

                def local_loss(p):
                    if sync_now:
                        p = overlap_lib.mark_buckets(p, buckets_now, axis)
                    with overlap_lib.scan_sync_scope(
                            axis if (sync_now and in_scan) else None):
                        out, new_state = forward(
                            model, {"params": p, "state": state}, mb,
                            True, rngs)
                    per_ex = loss_fn(out, mb)
                    w = mb.get("weight")
                    if w is not None:
                        lsum = jnp.sum(per_ex * w)
                        denom_local = jnp.sum(w)
                    else:
                        lsum = jnp.sum(per_ex)
                        # static local element count: psum'd into the
                        # exact global count the implicit mean divides by
                        denom_local = jnp.asarray(per_ex.size, jnp.float32)
                    return lsum, (new_state, out, denom_local)

                (lsum, (new_state, out, denom_local)), grads = \
                    jax.value_and_grad(local_loss, has_aux=True)(params)
                tot = lax.psum(
                    jnp.stack([lsum.astype(jnp.float32),
                               denom_local.astype(jnp.float32)]), axis)
                denom = (jnp.maximum(tot[1], 1e-9) if weighted else tot[1])
                loss = tot[0] / denom
                grads = jax.tree_util.tree_map(
                    lambda g: g / denom.astype(g.dtype), grads)
                return loss, new_state, out, grads

            # structure-only abstract pass: out_specs need the forward's
            # output tree (batch-led leaves rejoin the global batch
            # layout — the same batch-led contract shard_batch applies
            # to inputs; a non-divisible leading dim gets an actionable
            # error instead of shard_map's shape mismatch)
            out_s, state_s = jax.eval_shape(
                lambda p, s, b, r: forward(
                    model, {"params": p, "state": s}, b, True, r),
                params, state, mb, rngs)
            dp = dict(zip(mesh.axis_names,
                          mesh.devices.shape))[mesh_lib.DATA_AXIS]

            def out_spec(s):
                if not getattr(s, "ndim", 0):
                    return P()
                if s.shape[0] % dp:
                    raise ValueError(
                        f"grad_sync={mode!r} requires batch-led forward "
                        f"outputs: got an output leaf of shape {s.shape} "
                        f"whose leading dim does not divide the dp axis "
                        f"size {dp} (use grad_sync=None for this model, "
                        f"or make its outputs batch-led)")
                return P(mesh_lib.DATA_AXIS)

            sm = _sm(
                per_device,
                in_specs=(repl_of(params), repl_of(state),
                          jax.tree_util.tree_map(batch_spec, mb),
                          repl_of(rngs)),
                out_specs=(P(), repl_of(state_s),
                           jax.tree_util.tree_map(out_spec, out_s),
                           repl_of(params)))
            loss, new_state, out, grads = sm(params, state, mb, rngs)
            return (loss, (new_state, out)), grads

        def accum_sync(grads):
            def per_device(gs):
                return overlap_lib.apply_bucket_sync(gs, buckets_accum,
                                                     axis)
            return _sm(per_device, in_specs=(repl_of(grads),),
                       out_specs=repl_of(grads))(grads)

        return grads_fn, accum_sync

    # -- compiled steps ------------------------------------------------------

    def _make_step_fn(self, accum_axis: bool):
        """Build the one-optimizer-step function shared by the plain and
        fused paths.

        ``accum_axis=False``: ``batch`` is a single microbatch pytree — the
        plain step body, math unchanged from the single-dispatch trainer.

        ``accum_axis=True``: ``batch`` leaves carry a leading ``[M, ...]``
        microbatch axis. ``M == 1`` squeezes the axis and runs the identical
        plain body (so ``steps_per_call``-only fusion is bit-for-bit the
        plain step). ``M > 1`` runs a donated-accumulator ``lax.scan`` over
        the M microbatches: each microbatch's loss is its own weight-
        normalized mean, loss/grads are the mean of the M microbatch means
        (mean-of-means — mask/weight-correct within each microbatch), the
        module state threads sequentially, and the optimizer update fires
        once on the accumulated gradient.

        With health telemetry on, the step returns a 7th element: the
        per-step health-scalar dict (obs.health.health_scalars) — a few
        fused reduces over grads/updates/params that XLA folds into the
        step program, so monitoring never adds a dispatch."""
        health_on = self._health_on()
        opt = self.optimizer
        model = self.model
        loss_fn = self.loss_fn
        forward = self._forward
        evaluator = self.evaluator

        def microbatch_grads(params, state, mb, rngs):
            def compute_loss(p):
                out, new_state = forward(model, {"params": p, "state": state},
                                         mb, True, rngs)
                per_ex = loss_fn(out, mb)
                w = mb.get("weight")
                if w is not None:
                    loss = jnp.sum(per_ex * w) / jnp.maximum(jnp.sum(w), 1e-9)
                else:
                    loss = jnp.mean(per_ex)
                return loss, (new_state, out)

            return jax.value_and_grad(compute_loss, has_aux=True)(params)

        # Explicit dp gradient sync (ISSUE 8): swap the implicit-GSPMD
        # microbatch_grads for the manual-dp bucketed/fused path. With
        # grad_accum the markers stay OFF per microbatch (sync_now=False:
        # local grads accumulate) and accum_sync fires once per step.
        sync_mode = self._resolve_grad_sync()
        synced_grads = accum_sync = None
        if sync_mode is not None:
            synced_grads, accum_sync = self._make_synced_grads(sync_mode)

        def grads_of(params, state, mb, rngs, sync_now):
            if synced_grads is not None:
                return synced_grads(params, state, mb, rngs, sync_now)
            return microbatch_grads(params, state, mb, rngs)

        def step_fn(params, state, opt_state, step, batch, rng):
            M = (jax.tree_util.tree_leaves(batch)[0].shape[0]
                 if accum_axis else 1)
            if accum_axis and M == 1:
                batch = jax.tree_util.tree_map(lambda x: x[0], batch)
            if M == 1:
                rngs = {"dropout": jax.random.fold_in(rng, step)}
                (loss, (new_state, out)), grads = grads_of(
                    params, state, batch, rngs, True)
                stats = (evaluator.batch_stats(out, batch)
                         if evaluator is not None else {})
            else:
                step_key = jax.random.fold_in(rng, step)

                def micro(carry, xs):
                    st, gacc, lacc = carry
                    mb, midx = xs
                    rngs = {"dropout": jax.random.fold_in(step_key, midx)}
                    (l, (new_st, out)), g = grads_of(
                        params, st, mb, rngs, False)
                    s = (evaluator.batch_stats(out, mb)
                         if evaluator is not None else {})
                    gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
                    return (new_st, gacc, lacc + l), s

                g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
                (new_state, gacc, lacc), stats = lax.scan(
                    micro, (state, g0, jnp.zeros((), jnp.float32)),
                    (batch, jnp.arange(M)))
                grads = jax.tree_util.tree_map(lambda g: g / M, gacc)
                loss = lacc / M
                if accum_sync is not None:
                    # sync the ACCUMULATED gradient once per optimizer
                    # step — never per microbatch (the microbatch grads
                    # above were local per-device sums)
                    grads = accum_sync(grads)
            updates, new_opt = opt.update(grads, opt_state, params, step)
            new_params = apply_updates(params, updates)
            if health_on:
                from ..obs.health import health_scalars
                health = health_scalars(grads, updates, new_params, loss)
                return (new_params, new_state, new_opt, step + 1, loss,
                        stats, health)
            return new_params, new_state, new_opt, step + 1, loss, stats

        return self._in_mesh(step_fn)

    def _in_mesh(self, fn):
        """Trace ``fn`` with the trainer's mesh active
        (``core.mesh.use_mesh``): a layer that has to run per shard —
        a Mosaic kernel cannot be partitioned automatically — finds the
        mesh through ``current_mesh()``. Trace-time only."""
        mesh = self.mesh

        def traced(*args):
            with mesh_lib.use_mesh(mesh):
                return fn(*args)
        return traced

    def _build_train_step(self):
        step_fn = self._make_step_fn(accum_axis=False)
        # Shardings: batch sharded over the data axis, params replicated
        # (default) or committed to the user's model-parallel layout at
        # init — in that case shardings are taken from the committed inputs
        # and SPMD propagation lays out the rest. XLA inserts the gradient
        # all-reduce over ICI — the entire pserver tier collapses here.
        mesh = self.mesh
        donate = (0, 1, 2) if self._donate else ()
        if self._param_sharding is None:
            repl = NamedSharding(mesh, P())
            data = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
            self._train_step = jax.jit(
                step_fn,
                in_shardings=(repl, repl, repl, repl, data, repl),
                donate_argnums=donate)
        else:
            self._train_step = jax.jit(step_fn, donate_argnums=donate)

    def _build_fused_step(self, sample_batches):
        """The fused hot loop: ONE jit-compiled dispatch = a donated
        ``lax.scan`` over K optimizer steps (each itself scanning M
        microbatches when ``grad_accum > 1``). ``sample_batches`` (host
        leaves ``[K, M, batch, ...]``) fixes the batch-tree structure for the
        per-leaf data shardings; distinct (K, M) tail shapes retrace through
        the same jit cache. Returns the stacked per-step losses ``[K]`` and
        evaluator stats with leading ``[K, M]`` (``[K]`` when the microbatch
        axis was squeezed)."""
        step_fn = self._make_step_fn(accum_axis=True)
        mesh = self.mesh

        def fused_fn(params, state, opt_state, step, batches, rng):
            def body(carry, kbatch):
                p, st, o, s = carry
                out = step_fn(p, st, o, s, kbatch, rng)
                # ys = (loss, stats) or (loss, stats, health) — the scan
                # stacks each over the K steps
                return out[:4], out[4:]

            (params, state, opt_state, step), ys = lax.scan(
                body, (params, state, opt_state, step), batches)
            return (params, state, opt_state, step) + tuple(ys)

        donate = (0, 1, 2) if self._donate else ()
        if self._param_sharding is None:
            repl = NamedSharding(mesh, P())
            bshard = jax.tree_util.tree_map(self._fused_leaf_sharding,
                                            sample_batches)
            self._fused_step = jax.jit(
                fused_fn,
                in_shardings=(repl, repl, repl, repl, bshard, repl),
                donate_argnums=donate)
        else:
            self._fused_step = jax.jit(fused_fn, donate_argnums=donate)

    def _build_eval_step(self):
        model = self.model
        loss_fn = self.loss_fn
        forward = self._forward
        evaluator = self.evaluator

        def eval_fn(params, state, batch):
            out, _ = forward(model, {"params": params, "state": state},
                             batch, False, None)
            per_ex = loss_fn(out, batch)
            stats = (evaluator.batch_stats(out, batch)
                     if evaluator is not None else {})
            return jnp.mean(per_ex), stats

        self._eval_step = jax.jit(self._in_mesh(eval_fn))

    # -- loops ---------------------------------------------------------------

    def _shard(self, host_batch):
        return mesh_lib.shard_batch(self.mesh, host_batch)

    def train(self, reader: Callable, num_passes: int = 1,
              event_handler: Optional[Callable] = None,
              test_reader: Optional[Callable] = None,
              checkpoint_dir: Optional[str] = None,
              checkpoint_keep: int = 3,
              checkpoint_async: bool = False,
              saving_period: Optional[int] = None,
              log_period: int = 100, rng: Optional[jax.Array] = None,
              resume: bool = False) -> TrainState:
        """The pass/batch loop (v2 ``SGD.train`` surface + v1 pass checkpoints).

        ``checkpoint_async=True`` moves each checkpoint's CRC + disk write
        to a background thread (the device-state snapshot stays on the hot
        path; see :class:`~paddle_tpu.train.checkpoint.AsyncCheckpointer`) —
        the analog of the reference's off-critical-path checkpoint/commit
        work. The final save is fenced before ``train`` returns.

        ``saving_period``: also checkpoint every N batches *within* a pass
        (the reference's ``--saving_period_by_batches``,
        ``trainer/Trainer.cpp``), recording the data-iterator position.
        ``resume=True`` then continues mid-pass: with a deterministic
        ``reader`` the already-consumed batches of the interrupted pass are
        skipped, reproducing the uninterrupted run (the Go master's
        task-queue recovery, ``go/master/service.go:313``, done the
        single-controller way — deterministic data + iterator state in the
        checkpoint). Evaluator state is not checkpointed, so the resumed
        pass's metrics cover only its remaining batches.
        """
        assert self.train_state is not None, "call init() first"
        # a stop request is scoped to ONE train() call: a prior run's
        # consumed-or-unconsumed flag must not instantly preempt this one
        # (the handler can re-request once this run is live)
        self._stop_requested = None
        if self.anomaly is not None:
            # the flight recorder needs the trace ring and a lazy
            # config/env/mesh snapshot source for its bundles
            self.anomaly.bind(tracer=self.tracer,
                              context_fn=self._anomaly_context)
        fused = self.steps_per_call > 1 or self.grad_accum > 1
        if not fused and self._train_step is None:
            self._build_train_step()    # fused step builds lazily per group
        handler = event_handler or (lambda e: None)
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        start_pass, skip_batches = 0, 0
        if resume and checkpoint_dir:
            if ckpt_lib.latest_pass(checkpoint_dir) is not None:
                try:
                    # latest VALID pass: poisoned dirs are quarantined
                    # (renamed .corrupt, never deleted) and the chain
                    # falls back one pass — resume survives a corrupt
                    # latest checkpoint instead of dying on its CRC
                    self.restore(checkpoint_dir)
                except FileNotFoundError as e:
                    self.last_quarantined = list(
                        getattr(e, "quarantined", []))
                    _log.warning(
                        "resume: no readable checkpoint remains under %s "
                        "after quarantine — starting from scratch",
                        checkpoint_dir)
                else:
                    last = self._last_restored_pass
                    it = self._last_iter_state
                    if it is not None and not int(it.get("completed", 1)):
                        start_pass = int(it["pass"])
                        skip_batches = int(it["next_batch"])
                    else:
                        start_pass = last + 1

        saver = None
        if checkpoint_async:
            saver = ckpt_lib.AsyncCheckpointer(telemetry=self.telemetry,
                                               faults=self.faults)
            save_fn = saver.save
        elif self.faults is not None:
            import functools
            save_fn = functools.partial(ckpt_lib.save_checkpoint,
                                        faults=self.faults)
        else:
            save_fn = ckpt_lib.save_checkpoint
        try:
            return self._train_loop(reader, num_passes, handler, test_reader,
                                    checkpoint_dir, checkpoint_keep,
                                    saving_period, log_period, rng,
                                    start_pass, skip_batches, save_fn)
        finally:
            if saver is not None:
                saver.close()          # fence the in-flight write

    def _train_loop(self, reader, num_passes, handler, test_reader,
                    checkpoint_dir, checkpoint_keep, saving_period,
                    log_period, rng, start_pass, skip_batches, save_fn):
        fused = self.steps_per_call > 1 or self.grad_accum > 1
        tel = self.telemetry
        for pass_id in range(start_pass, num_passes):
            handler(ev.BeginPass(pass_id))
            if tel is not None:
                tel.begin_pass(pass_id)   # reset the per-pass memory peak
            if self.evaluator is not None:
                self.evaluator.reset()
            # re-anchor the host-side step mirror (train_state is quiesced
            # at pass boundaries: serial mode syncs per group, pipelined
            # mode drained at the previous pass end)
            self._host_step = int(jax.device_get(self.train_state.step))
            costs = []
            pipe = None
            if fused and self.pipeline_depth > 1:
                from .host_pipeline import FusedPipeline
                pipe = FusedPipeline(
                    self, pass_id, rng, handler, costs, log_period,
                    saving_period, checkpoint_dir, checkpoint_keep, save_fn,
                    depth=self.pipeline_depth)
            try:
                self._run_pass(
                    reader, pass_id, start_pass, skip_batches, pipe,
                    handler, costs, log_period, saving_period,
                    checkpoint_dir, checkpoint_keep, save_fn, rng)
            finally:
                if pipe is not None:
                    pipe.close()
            pass_metrics = (self.evaluator.result()
                            if self.evaluator is not None else {})
            pass_metrics["mean_cost"] = float(np.mean(costs)) if costs else 0.0
            if test_reader is not None:
                tc, tm = self.evaluate(test_reader)
                pass_metrics.update({f"test_{k}": v for k, v in tm.items()})
                pass_metrics["test_cost"] = tc
            if checkpoint_dir:
                with tspan(self.tracer, "checkpoint_save", pass_end=pass_id):
                    save_fn(
                        checkpoint_dir, pass_id,
                        {**self.train_state.as_dict(),
                         "iter": {"pass": pass_id, "next_batch": 0,
                                  "completed": 1}},
                        keep_last=checkpoint_keep)
            handler(ev.EndPass(pass_id, pass_metrics))
            if self._stop_requested is not None:
                # a stop that arrived too late for a group boundary (or
                # during eval / the pass-end save) exits here: the pass
                # checkpoint above already recorded completed=1, so the
                # resume position is the next pass's first batch
                raise Preempted(pass_id=pass_id + 1, next_batch=0,
                                reason=self._stop_requested)
        return self.train_state

    def _run_pass(self, reader, pass_id, start_pass, skip_batches, pipe,
                  handler, costs, log_period, saving_period, checkpoint_dir,
                  checkpoint_keep, save_fn, rng):
        """One pass's batch loop (split out of ``_train_loop`` so the fused
        pipeline's stager thread is always closed via try/finally). The
        serial paths are byte-identical to the pre-pipeline loop."""
        tel = self.telemetry
        fused = self.steps_per_call > 1 or self.grad_accum > 1
        group = self.steps_per_call * self.grad_accum
        # The plain loop defers its loss fetch only with nan_check off: the
        # finiteness trap's contract is raise-before-the-next-dispatch.
        plain_deferred = (not fused and self.pipeline_depth > 1
                          and not self._nan_check)
        ts = self.train_state
        params, state, opt_state, step = (ts.params, ts.state, ts.opt_state,
                                          ts.step)
        buf, buf_start = [], 0
        pending = []              # plain deferred-fetch in-flight window
        batches = enumerate(reader())
        while True:
            with tspan(self.tracer, "reader_wait"):
                item = next(batches, None)
            if item is None:
                break
            batch_id, host_batch = item
            if pass_id == start_pass and batch_id < skip_batches:
                # Deterministic replay skip on resume. On the last
                # skipped batch, compare against the fingerprint the
                # checkpoint recorded for it — a mismatch means the
                # reader is not deterministic and the resumed pass
                # would train on a different batch remainder.
                if batch_id == skip_batches - 1:
                    want = (self._last_iter_state or {}).get("batch_crc")
                    if want is not None and \
                            _batch_fingerprint(host_batch) != int(want):
                        _log.warning(
                            "resume: reader replay diverged from the "
                            "checkpointed batch fingerprint at batch %d "
                            "— the reader is nondeterministic (shuffle/"
                            "buffered?); the resumed pass trains on a "
                            "different batch remainder than the "
                            "interrupted run", batch_id)
                continue
            if fused:
                # Buffer K*M host batches, then ONE device dispatch for
                # K optimizer steps; host bookkeeping replays after. A
                # shape change mid-group (ragged final reader batch)
                # flushes the buffer early — groups must stack. With a
                # pipe (pipeline_depth > 1) the group goes to the stager
                # thread instead of being stacked/dispatched serially.
                if buf and _batch_shapes(host_batch) != \
                        _batch_shapes(buf[0]):
                    if pipe is not None:
                        pipe.submit(buf, buf_start)
                    else:
                        self._run_fused_group(
                            buf, buf_start, pass_id, rng, handler, costs,
                            log_period, saving_period, checkpoint_dir,
                            checkpoint_keep, save_fn)
                    buf = []
                if not buf:
                    buf_start = batch_id
                buf.append(host_batch)
                if len(buf) == group:
                    if pipe is not None:
                        pipe.submit(buf, buf_start)
                    else:
                        self._run_fused_group(
                            buf, buf_start, pass_id, rng, handler, costs,
                            log_period, saving_period, checkpoint_dir,
                            checkpoint_keep, save_fn)
                    buf = []
                    # graceful stop lands on exactly this boundary: the
                    # group's batches are all dispatched (buf empty), so
                    # after the drain inside _maybe_stop the state is
                    # quiesced at batch_id + 1 consumed batches
                    self._maybe_stop(pipe, pending, pass_id, batch_id + 1,
                                     handler, costs, log_period,
                                     checkpoint_dir, checkpoint_keep,
                                     save_fn, last_batch=host_batch)
                continue
            if plain_deferred:
                # The plain loop's deferred-fetch window: dispatch now,
                # replay the host bookkeeping (Begin/EndIteration both —
                # like fused mode) when the window drains. nan_check off
                # by construction. Make room BEFORE dispatching (like
                # FusedPipeline) so at most pipeline_depth calls are ever
                # in flight.
                while len(pending) >= self.pipeline_depth:
                    self._replay_plain(
                        pending.pop(0), pass_id, handler, costs,
                        log_period, checkpoint_dir, checkpoint_keep,
                        save_fn)
                params, state, opt_state, step = self._plain_dispatch(
                    host_batch, pass_id, batch_id, params, state,
                    opt_state, step, rng, tel, pending, saving_period,
                    checkpoint_dir)
                if pending[-1]["boundary"]:
                    # checkpoint boundary: the save needs train_state
                    # quiesced at exactly this batch — drain everything
                    # before the next dispatch advances it
                    while pending:
                        self._replay_plain(
                            pending.pop(0), pass_id, handler, costs,
                            log_period, checkpoint_dir, checkpoint_keep,
                            save_fn)
                self._maybe_stop(None, pending, pass_id, batch_id + 1,
                                 handler, costs, log_period,
                                 checkpoint_dir, checkpoint_keep, save_fn,
                                 last_batch=host_batch)
                continue
            # SERIAL plain step. _plain_dispatch/_replay_plain mirror this
            # body for the deferred-fetch window (divergences are the
            # point: BeginIteration pre-dispatch here, the per-call fence,
            # int(step) fetches) — a bookkeeping change here must be
            # mirrored there. It stays in this frame: a method of its own
            # would put one more Python frame under the jit call (see
            # _timed) and keep the donated trees alive in this one until
            # it returned, after the loss fetch, where the chip waits.
            with tspan(self.tracer, "train_step", batch=batch_id,
                       step=self._host_step):
                with tspan(self.tracer, "events", event="BeginIteration"):
                    handler(ev.BeginIteration(pass_id, batch_id))
                is_new, fp = False, None
                if tel is not None:
                    fp = ((1, 1),) + _step_fingerprint(host_batch)
                    is_new = tel.observe_fingerprint(fp)
                with self._timed("shard_batch", "device_put",
                                 batch=batch_id) as shard:
                    batch = self._shard(host_batch)
                hlo_flops = None
                if is_new:
                    from ..obs.telemetry import lowered_hlo_flops
                    try:
                        hlo_flops = lowered_hlo_flops(self._train_step.lower(
                            params, state, opt_state, step, batch, rng))
                    except Exception:
                        hlo_flops = None
                # dispatch timing starts AFTER the FLOPs lowering — the
                # measurement layer must not bill its own extra trace to
                # the step it measures (the fused path does the same)
                with self._timed("train_step", "dispatch", self._train_step,
                                 batch=batch_id, new_compile=is_new) as disp:
                    out, profiled = self._maybe_profiled_call(
                        self._train_step, params, state, opt_state, step,
                        batch, rng)
                dispatch_s = disp.seconds
                params, state, opt_state, step = out[:4]
                loss, stats = out[4], out[5]
                health = out[6] if len(out) > 6 else None
                device_s = None
                if tel is not None and tel.fence:
                    # the fencing rule: the dispatch above returned as soon
                    # as the program was enqueued — device time needs a sync
                    with self._timed("device_wait", "fence",
                                     batch=batch_id) as fence:
                        jax.block_until_ready((params, loss))
                    device_s = fence.seconds
                if is_new:
                    tel.record_compile(
                        fp, wall_s=dispatch_s + (device_s or 0.0),
                        hlo_flops=hlo_flops, meta={"k_steps": 1, "m": 1})
                # Refresh train_state every step: with buffer donation the
                # previous arrays are invalidated, and event handlers may read
                # trainer.train_state (e.g. to save) mid-pass.
                self.train_state = TrainState(params, state, opt_state, step)
                self._host_step += 1
                with tspan(self.tracer, "loss_fetch", batch=batch_id):
                    cost = float(loss)     # the host waits for the device
                if tel is not None:
                    if health is not None:
                        tel.update_health(jax.device_get(health))
                    rec = tel.emit_step(
                        {"pass": pass_id, "step": int(step),
                         "k_steps": 1, "m": 1, "loss": cost,
                         "profiled": profiled,
                         "host_stack_ms": None,
                         "shard_ms": round(shard.seconds * 1e3, 3),
                         "dispatch_ms": round(dispatch_s * 1e3, 3),
                         "device_ms": (round(device_s * 1e3, 3)
                                       if device_s is not None else None),
                         "replay_ms": None})
                    with tspan(self.tracer, "events", event="TelemetryRecord"):
                        handler(ev.TelemetryRecord(record=rec))
                    self._anomaly_observe(rec)
                if self._nan_check and not np.isfinite(cost):
                    from ..utils import debug as dbg
                    bad = dbg.nonfinite_leaves(
                        {"params": params, "state": state})
                    raise FloatingPointError(
                        f"non-finite loss {cost} at pass {pass_id} batch "
                        f"{batch_id} (step {int(step)}); non-finite leaves: "
                        f"{bad[:8] or 'none (loss only)'}")
                costs.append(cost)
                metrics = {}
                if self.evaluator is not None:
                    self.evaluator.update(jax.device_get(stats))
                    metrics = self.evaluator.result()
                if log_period and (batch_id + 1) % log_period == 0:
                    msg = " ".join(f"{k}={v:.4f}" for k, v in metrics.items())
                    if tel is not None and tel.last_health:
                        # health monitors are fetched per call (riding the
                        # same sync as the loss) but LOGGED only here
                        msg += " " + " ".join(
                            f"{k}={v:.3g}"
                            for k, v in tel.last_health.items())
                    _log.info("pass %d batch %d cost=%.4f %s",
                              pass_id, batch_id + 1, cost, msg)
                    self._log_stat_report()
                if self._param_stats_period and \
                        (batch_id + 1) % self._param_stats_period == 0:
                    self._log_param_stats(pass_id, batch_id)
                if saving_period and checkpoint_dir and \
                        (batch_id + 1) % saving_period == 0:
                    with tspan(self.tracer, "checkpoint_save",
                               next_batch=batch_id + 1):
                        save_fn(
                            checkpoint_dir, pass_id,
                            {**self.train_state.as_dict(),
                             "iter": {"pass": pass_id,
                                      "next_batch": batch_id + 1,
                                      "completed": 0,
                                      "batch_crc":
                                          _batch_fingerprint(host_batch)}},
                            keep_last=checkpoint_keep)
                with tspan(self.tracer, "events", event="EndIteration"):
                    handler(ev.EndIteration(pass_id, batch_id, int(step), cost,
                                            metrics))
                if self.faults is not None:
                    self._fire_step_faults(self._host_step)
            self._maybe_stop(None, pending, pass_id, batch_id + 1, handler,
                             costs, log_period, checkpoint_dir,
                             checkpoint_keep, save_fn,
                             last_batch=host_batch)
        if fused and buf:
            # Pass tail smaller than K*M: flush what's buffered (the
            # final optimizer step may accumulate < M microbatches;
            # its loss/grads average over the actual count).
            if pipe is not None:
                pipe.submit(buf, buf_start)
            else:
                self._run_fused_group(
                    buf, buf_start, pass_id, rng, handler, costs,
                    log_period, saving_period, checkpoint_dir,
                    checkpoint_keep, save_fn)
        if pipe is not None:
            pipe.flush()          # pass end drains the whole window (FIFO)
        while pending:
            self._replay_plain(pending.pop(0), pass_id, handler, costs,
                               log_period, checkpoint_dir, checkpoint_keep,
                               save_fn)

    # -- plain deferred-fetch (pipeline_depth > 1, K=1, M=1) -----------------

    def _plain_dispatch(self, host_batch, pass_id, batch_id, params, state,
                        opt_state, step, rng, tel, pending, saving_period,
                        checkpoint_dir):
        """Dispatch ONE plain step without fetching anything; append a
        pending entry for the deferred replay. Returns the new device-side
        carry. No per-call fence even with telemetry on (it would serialize
        the window); the drain records ``drain_wait_ms``.

        This + ``_replay_plain`` mirror the SERIAL plain body in
        ``_run_pass`` minus every host sync (fence, ``float(loss)``,
        ``int(step)`` — replaced by the ``_host_step`` mirror) — keep the
        bookkeeping in lockstep when editing either."""
        is_new, fp = False, None
        if tel is not None:
            fp = ((1, 1),) + _step_fingerprint(host_batch)
            is_new = tel.observe_fingerprint(fp)
        with self._timed("shard_batch", "device_put",
                         batch=batch_id) as shard:
            batch = self._shard(host_batch)
        hlo_flops = None
        if is_new:
            from ..obs.telemetry import lowered_hlo_flops
            try:
                hlo_flops = lowered_hlo_flops(self._train_step.lower(
                    params, state, opt_state, step, batch, rng))
            except Exception:
                hlo_flops = None
        with self._timed("train_step", "dispatch", self._train_step,
                         batch=batch_id, new_compile=is_new) as disp:
            out, profiled = self._maybe_profiled_call(
                self._train_step, params, state, opt_state, step, batch,
                rng)
        dispatch_s = disp.seconds
        params, state, opt_state, step = out[:4]
        if is_new:
            tel.record_compile(fp, wall_s=dispatch_s, hlo_flops=hlo_flops,
                               meta={"k_steps": 1, "m": 1})
        self.train_state = TrainState(params, state, opt_state, step)
        self._host_step += 1
        boundary = bool(saving_period and checkpoint_dir
                        and (batch_id + 1) % saving_period == 0)
        rec = None
        if tel is not None:
            rec = {"pass": pass_id, "step": self._host_step,
                   "k_steps": 1, "m": 1, "profiled": profiled,
                   "host_stack_ms": None,
                   "shard_ms": round(shard.seconds * 1e3, 3),
                   "dispatch_ms": round(dispatch_s * 1e3, 3),
                   "device_ms": None, "replay_ms": None}
        pending.append({
            "batch_id": batch_id, "step": self._host_step,
            "loss": out[4], "stats": out[5],
            "health": out[6] if len(out) > 6 else None,
            "rec": rec, "boundary": boundary,
            "crc": _batch_fingerprint(host_batch) if boundary else None})
        return params, state, opt_state, step

    def _replay_plain(self, entry, pass_id, handler, costs, log_period,
                      checkpoint_dir, checkpoint_keep, save_fn):
        """Deferred host bookkeeping for one plain step, replayed at drain
        in dispatch order — the plain-loop analog of ``_post_fused``
        (Begin/EndIteration both fire here, post-dispatch, like fused
        mode; the observable event sequence matches the serial loop
        exactly). ``nan_check`` is off by construction on this path."""
        tel = self.telemetry
        batch_id = entry["batch_id"]
        handler(ev.BeginIteration(pass_id, batch_id))
        with self._timed("drain_wait", "drain_wait",
                         batch=batch_id) as drain:
            cost = float(np.asarray(jax.device_get(entry["loss"])))
        drain_wait = drain.seconds
        if tel is not None:
            if entry["health"] is not None:
                tel.update_health(jax.device_get(entry["health"]))
            rec = entry["rec"]
            rec["loss"] = cost
            rec["drain_wait_ms"] = round(drain_wait * 1e3, 3)
            rec = tel.emit_step(rec)
            handler(ev.TelemetryRecord(record=rec))
            self._anomaly_observe(rec)
        costs.append(cost)
        metrics = {}
        if self.evaluator is not None:
            self.evaluator.update(jax.device_get(entry["stats"]))
            metrics = self.evaluator.result()
        if log_period and (batch_id + 1) % log_period == 0:
            msg = " ".join(f"{k}={v:.4f}" for k, v in metrics.items())
            if tel is not None and tel.last_health:
                msg += " " + " ".join(f"{k}={v:.3g}"
                                      for k, v in tel.last_health.items())
            _log.info("pass %d batch %d cost=%.4f %s",
                      pass_id, batch_id + 1, cost, msg)
            self._log_stat_report()
        if self._param_stats_period and \
                (batch_id + 1) % self._param_stats_period == 0:
            self._log_param_stats(pass_id, batch_id)
        if entry["boundary"]:
            # the boundary forced a full drain right after this batch's
            # dispatch, so train_state is quiesced at exactly this step
            with tspan(self.tracer, "checkpoint_save",
                       next_batch=batch_id + 1):
                save_fn(
                    checkpoint_dir, pass_id,
                    {**self.train_state.as_dict(),
                     "iter": {"pass": pass_id, "next_batch": batch_id + 1,
                              "completed": 0, "batch_crc": entry["crc"]}},
                    keep_last=checkpoint_keep)
        handler(ev.EndIteration(pass_id, batch_id, entry["step"], cost,
                                metrics))
        if self.faults is not None:
            self._fire_step_faults(entry["step"])

    # -- fused dispatch ------------------------------------------------------

    @staticmethod
    def _plan_group(n: int, m: int):
        """Split an n-batch group buffer into dispatch slices: the full
        KxM part first, then the tail (whose final optimizer step may
        accumulate < M microbatches). Returns [(offset, take, m_eff)] —
        shared by the serial loop and the stager thread so pipelined
        grouping is always in lockstep with serial grouping."""
        plans, done = [], 0
        while done < n:
            rem = n - done
            take = (rem // m) * m or rem
            plans.append((done, take, m if take >= m else take))
            done += take
        return plans

    def _stage_group_work(self, work):
        """Stage one raw group buffer — stack + device_put every dispatch
        slice via the shared ``_fused_leaf_sharding`` rule. RUNS IN THE
        STAGER THREAD: touches no trainer mutable state (StatSet is
        locked), so it can overlap the in-flight device calls."""
        from .host_pipeline import StagedGroup, StagedUnit
        buf, buf_start, boundary = work
        if self.faults is not None:
            # the stager injection point: raises IN THE WORKER THREAD, so
            # the failure travels GroupStager's producer-error path and
            # surfaces in the training thread at the next submit/get
            self.faults.maybe_stager_error(buf_start)
        tracer = live(self.tracer)
        # the group's flow id links THIS thread's staging span to the main
        # thread's later dispatch + drain spans in the trace viewer
        flow = tracer.new_flow() if tracer is not None else None
        units = []
        with tspan(tracer, "stage", flow_start=flow, group=buf_start,
                   batches=len(buf)):
            for off, take, m_eff in self._plan_group(len(buf),
                                                     self.grad_accum):
                with self._timed("stage_stack", "stack", group=buf_start,
                                 offset=off) as stack:
                    stacked = self._stack_group(buf[off:off + take],
                                                take // m_eff, m_eff)
                with self._timed("stage_shard", "shard", group=buf_start,
                                 offset=off) as shard:
                    staged = self._shard_fused(stacked)
                units.append(StagedUnit(offset=off, m_eff=m_eff,
                                        batches=staged,
                                        stack_s=stack.seconds,
                                        shard_s=shard.seconds))
            crc = _batch_fingerprint(buf[-1]) if boundary else None
        return StagedGroup(buf_start=buf_start, buf_len=len(buf),
                           units=units, boundary=boundary, crc=crc,
                           flow=flow)

    def _stack_group(self, sub, k: int, m: int):
        """Stack k*m host batches into one pytree with leaves
        ``[k, m, batch, ...]`` (the compiled fused step's input layout)."""
        hosts = [jax.tree_util.tree_map(np.asarray, b) for b in sub]

        def stack(*xs):
            arr = np.stack(xs)
            return arr.reshape((k, m) + arr.shape[1:])

        return jax.tree_util.tree_map(stack, *hosts)

    def _fused_leaf_sharding(self, x):
        """The ONE per-leaf layout rule for stacked [K, M, batch, ...] group
        leaves — shared by the compiled step's in_shardings and the host
        device_put so the dispatch never resharding-copies its input:
        microbatch dim sharded over the data axis, [K, M] leading dims (and
        per-batch scalars) replicated."""
        if np.ndim(x) <= 2:               # [K, M] scalars: replicated
            return NamedSharding(self.mesh, P())
        return NamedSharding(self.mesh, P(None, None, mesh_lib.DATA_AXIS))

    def _shard_fused(self, stacked):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._fused_leaf_sharding(x)),
            stacked)

    def _dispatch_fused(self, stacked, rng, stack_s=None, staged=None,
                        defer=False, flow=None):
        """One fused device call; refreshes train_state (donation invalidates
        the previous buffers). Returns ``(losses [K], stats [K(, M), ...],
        health_or_None, record_or_None)`` — ``health`` is the device-side
        [K]-stacked health pytree (no fetch here), ``record`` the partial
        telemetry step record (breakdown fields filled; the events-replay
        time is appended by the caller).

        ``staged`` (a :class:`host_pipeline.StagedUnit`) supplies a group
        already stacked and device_put by the stager thread — the main
        thread skips both. ``defer=True`` (pipelined mode) additionally
        skips the per-call telemetry fence: a ``block_until_ready`` here
        would serialize exactly the window the pipeline keeps in flight;
        the drain records ``drain_wait_ms`` instead (``device_ms`` stays
        None, ``fenced`` False).

        Telemetry-off takes the exact pre-obs path: no fingerprinting, no
        fencing, no extra fetches — the dispatch count and donation
        behavior are byte-identical (tests/test_obs.py pins this)."""
        if staged is not None:
            stacked = staged.batches     # metadata-only uses below
            stack_s = staged.stack_s
        if self._fused_step is None:
            self._build_fused_step(stacked)
        tel = self.telemetry
        is_new, fp, hlo_flops = False, None, None
        if tel is not None:
            fp = _step_fingerprint(stacked)
            is_new = tel.observe_fingerprint(fp)
        if staged is not None:
            batches, shard_s = staged.batches, staged.shard_s
        else:
            with self._timed("shard_batch", "device_put") as shard:
                batches = self._shard_fused(stacked)
            shard_s = shard.seconds
        ts = self.train_state
        args = (ts.params, ts.state, ts.opt_state, ts.step, batches, rng)
        if is_new:
            # HLO cost-analysis FLOPs from the UN-compiled Lowered: one
            # extra trace (cheap), not a second compile; feeds MFU.
            from ..obs.telemetry import lowered_hlo_flops
            try:
                hlo_flops = lowered_hlo_flops(self._fused_step.lower(*args))
            except Exception:
                hlo_flops = None
        with self._timed("train_step", "dispatch", self._fused_step,
                         flow_step=flow, step=self._host_step,
                         new_compile=is_new) as disp:
            out, profiled = self._maybe_profiled_call(self._fused_step,
                                                      *args)
        dispatch_s = disp.seconds
        params, state, opt_state, step = out[:4]
        losses, stats = out[4], out[5]
        health = out[6] if len(out) > 6 else None
        device_s = None
        if tel is not None and tel.fence and not defer:
            # The fencing rule: the jit call above returns once XLA has
            # ENQUEUED the program (async dispatch) — a wall timer around
            # it measures dispatch, not compute. True device time is the
            # extra wait until the outputs are ready. Telemetry owns this
            # sync; without telemetry the loop never fences.
            with self._timed("device_wait", "fence") as fence:
                jax.block_until_ready((params, losses))
            device_s = fence.seconds
        k_eff = int(losses.shape[0])
        self._host_step += k_eff       # host mirror of the device step
        if is_new:
            tel.record_compile(
                fp, wall_s=dispatch_s + (device_s or 0.0),
                hlo_flops=hlo_flops,
                meta={"k_steps": k_eff,
                      "m": int(jax.tree_util.tree_leaves(stacked)[0]
                               .shape[1])})
        rec = None
        if tel is not None:
            rec = {"k_steps": k_eff,
                   "m": int(jax.tree_util.tree_leaves(stacked)[0].shape[1]),
                   # profiled calls carry a block_until_ready INSIDE the
                   # dispatch window (the capture must include compute) —
                   # their dispatch_ms is not comparable, so telemetry
                   # suppresses throughput and the anomaly detector skips
                   # the record (the flight recorder must not trigger the
                   # detector that armed it)
                   "profiled": profiled,
                   "host_stack_ms": (round(stack_s * 1e3, 3)
                                     if stack_s is not None else None),
                   "shard_ms": round(shard_s * 1e3, 3),
                   "dispatch_ms": round(dispatch_s * 1e3, 3),
                   "device_ms": (round(device_s * 1e3, 3)
                                 if device_s is not None else None)}
            if staged is not None:
                # background staging wall (stack + device_put, off the
                # critical path); drain_wait_ms/overlap_frac land at drain.
                # NOTE the semantic shift: in this record host_stack_ms/
                # shard_ms were measured on the STAGER thread (their sum
                # is stage_ms — already-hidden cost), unlike serial
                # records where they are main-thread critical-path time;
                # the exposed-cost signal for pipelined runs is
                # drain_wait_ms.
                rec["stage_ms"] = round(
                    (staged.stack_s + staged.shard_s) * 1e3, 3)
        self.train_state = TrainState(params, state, opt_state, step)
        return losses, stats, health, rec

    def _run_fused_group(self, buf, buf_start, pass_id, rng, handler, costs,
                         log_period, saving_period, checkpoint_dir,
                         checkpoint_keep, save_fn):
        """Dispatch a buffered host-batch group as fused device calls, then
        replay the per-optimizer-step host bookkeeping (events, costs,
        evaluator updates, logging) and checkpoint at the call boundary.

        Events fire with ``batch_id`` = the index of the step's LAST host
        batch, so host-batch-denominated periods (``log_period``,
        ``saving_period``) keep their plain-mode meaning. Because the K
        steps run inside one dispatch, Begin/EndIteration both fire after
        the call, and mid-pass checkpoints land on call boundaries (a
        ``saving_period`` crossed mid-call saves once, at the boundary, with
        the true ``next_batch`` position — so resume replay stays aligned
        with the fused grouping)."""
        results = []
        with tspan(self.tracer, "plan", group=buf_start, batches=len(buf)):
            plans = self._plan_group(len(buf), self.grad_accum)
        for off, take, m_eff in plans:
            with self._timed("stack_group", "stack", group=buf_start,
                             offset=off) as stack:
                stacked = self._stack_group(buf[off:off + take],
                                            take // m_eff, m_eff)
            stack_s = stack.seconds
            losses, stats, health, rec = self._dispatch_fused(
                stacked, rng, stack_s=stack_s)
            # record THIS dispatch's post-call step count: a group split
            # into several dispatches (tail not a multiple of M) must not
            # number earlier dispatches' steps off the later ones' state
            results.append((buf_start + off, m_eff, losses, stats,
                            self._host_step, health, rec))
        self._finalize_group(pass_id, buf_start, len(buf), results, handler,
                             costs, log_period, saving_period,
                             checkpoint_dir, checkpoint_keep, save_fn,
                             crc_fn=lambda: _batch_fingerprint(buf[-1]))

    def _finalize_group(self, pass_id, buf_start, buf_len, results, handler,
                        costs, log_period, saving_period, checkpoint_dir,
                        checkpoint_keep, save_fn, crc_fn,
                        drain_timing=False, overlap_frac=None):
        """The bottom half of a group: boundary checkpoint, then the FIFO
        event replay — shared verbatim by the serial loop (immediately
        after the dispatches) and the pipelined drain (deferred until the
        window releases the group). ``crc_fn`` supplies the group's last
        host-batch fingerprint (serial: computed lazily at save; pipelined:
        precomputed in the stager). ``drain_timing=True`` times the first
        blocking loss fetch per dispatch into ``drain_wait_ms`` and stamps
        ``overlap_frac`` — the pipelined replacements for the per-call
        device fence the pipeline cannot afford."""
        tel = self.telemetry
        if drain_timing:
            timed = []
            for i, (start, m_eff, losses, stats, step_after, health,
                    rec) in enumerate(results):
                with self._timed("drain_wait", "drain_wait",
                                 step=step_after) as drain:
                    losses = np.asarray(jax.device_get(losses))
                wait = drain.seconds
                if rec is not None:
                    rec["drain_wait_ms"] = round(wait * 1e3, 3)
                    if overlap_frac is not None:
                        rec["overlap_frac"] = round(overlap_frac, 4)
                timed.append((start, m_eff, losses, stats, step_after,
                              health, rec))
            results = timed
        # The boundary checkpoint lands BEFORE the replayed events, matching
        # the plain loop's save-then-EndIteration order (handlers that kill
        # training after a period save — the kill/resume pattern — observe
        # the same sequence). With nan_check on, a non-finite loss anywhere
        # in the group SKIPS the save (plain mode raises before reaching its
        # save) — never persist a poisoned train_state that resume would
        # restore.
        end = buf_start + buf_len
        group_finite = (not self._nan_check) or all(
            np.isfinite(np.asarray(jax.device_get(losses))).all()
            for _, _, losses, _, _, _, _ in results)
        if saving_period and checkpoint_dir and group_finite and \
                (end // saving_period) > (buf_start // saving_period):
            with tspan(self.tracer, "checkpoint_save", next_batch=end):
                save_fn(
                    checkpoint_dir, pass_id,
                    {**self.train_state.as_dict(),
                     "iter": {"pass": pass_id, "next_batch": end,
                              "completed": 0,
                              "batch_crc": crc_fn()}},
                    keep_last=checkpoint_keep)
        for start, m_eff, losses, stats, step_after, health, rec in results:
            # Health scalars are device-side [K] stacks; fetching them here
            # rides the same per-call host sync that already fetches the
            # losses — no extra dispatch. The human-readable log still
            # fires only at log_period (inside _post_fused).
            health_np = (jax.device_get(health)
                         if (tel is not None and health is not None)
                         else None)
            replay = self._timed(None, "events_replay", step=step_after)
            replay_ok = False
            try:
                with replay:
                    self._post_fused(pass_id, start, m_eff, losses, stats,
                                     step_after, handler, costs, log_period,
                                     health_np=health_np)
                replay_ok = True
            finally:
                # the record is emitted (and the anomaly detector fed) even
                # when nan_check's FloatingPointError unwinds _post_fused —
                # the plain loop observes before its raise, and a poisoned
                # run is EXACTLY when the flight recorder must fire. While
                # unwinding, a secondary failure here (a raising handler,
                # a dead transport under the loss fetch) must NOT mask the
                # original error and its nonfinite-leaves diagnostic.
                if tel is not None and rec is not None:
                    # success sentinel, NOT sys.exc_info(): the latter is
                    # non-None for the whole call when train() itself runs
                    # inside a caller's except block, which would silently
                    # swallow healthy-path handler bugs
                    unwinding = not replay_ok
                    try:
                        if health_np is not None:
                            tel.update_health(
                                {k: v[-1] for k, v in health_np.items()})
                        rec["pass"] = pass_id
                        rec["step"] = step_after
                        rec["loss"] = float(np.asarray(
                            jax.device_get(losses)).ravel()[-1])
                        rec["replay_ms"] = round(replay.seconds * 1e3, 3)
                        rec = tel.emit_step(rec)
                        handler(ev.TelemetryRecord(record=rec))
                        self._anomaly_observe(rec)
                    except Exception:
                        if not unwinding:
                            raise
                        _log.exception(
                            "telemetry emit failed during exception unwind "
                            "(the original error propagates)")

    def _post_fused(self, pass_id, start_index, m_eff, losses, stats,
                    step_after, handler, costs, log_period, health_np=None):
        """Replay one dispatch's host bookkeeping; ``step_after`` is the
        global optimizer-step count right after THAT dispatch.
        ``health_np``: host-fetched dict of [K] health scalars (telemetry
        on) — logged at log_period crossings."""
        losses_np = np.asarray(jax.device_get(losses))
        stats_np = (jax.device_get(stats)
                    if self.evaluator is not None else None)
        K = int(losses_np.shape[0])
        for k in range(K):
            last_id = start_index + (k + 1) * m_eff - 1
            handler(ev.BeginIteration(pass_id, last_id))
            cost = float(losses_np[k])
            if self._nan_check and not np.isfinite(cost):
                from ..utils import debug as dbg
                ts = self.train_state
                bad = dbg.nonfinite_leaves(
                    {"params": ts.params, "state": ts.state})
                raise FloatingPointError(
                    f"non-finite loss {cost} at pass {pass_id} batch "
                    f"{last_id} (step {step_after - (K - 1 - k)}); "
                    f"non-finite leaves (post-call state): "
                    f"{bad[:8] or 'none (loss only)'}")
            costs.append(cost)
            metrics = {}
            if self.evaluator is not None:
                for m in range(m_eff):
                    self.evaluator.update(jax.tree_util.tree_map(
                        lambda x: x[k] if m_eff == 1 else x[k][m], stats_np))
                metrics = self.evaluator.result()
            # Period checks use boundary CROSSING, not exact modulo: a step
            # consumes m_eff host batches, so (last_id + 1) only lands on
            # multiples of m_eff and an exact-modulo period not divisible
            # by grad_accum would (mostly) never fire.
            step_first = start_index + k * m_eff
            if log_period and \
                    (last_id + 1) // log_period > step_first // log_period:
                msg = " ".join(f"{k_}={v:.4f}" for k_, v in metrics.items())
                if health_np is not None:
                    msg += " " + " ".join(
                        f"{hk}={float(hv[k]):.3g}"
                        for hk, hv in health_np.items())
                _log.info("pass %d batch %d cost=%.4f %s",
                          pass_id, last_id + 1, cost, msg)
                self._log_stat_report()
            psp = self._param_stats_period
            if psp and (last_id + 1) // psp > step_first // psp:
                self._log_param_stats(pass_id, last_id)
            handler(ev.EndIteration(pass_id, last_id,
                                    step_after - (K - 1 - k), cost, metrics))
            if self.faults is not None:
                self._fire_step_faults(step_after - (K - 1 - k))

    def _log_stat_report(self, top_n: int = 8):
        """Periodic StatSet summary at log_period — the reference's
        ``printAllStatus`` analog (``utils/Stat.h``). INFO when telemetry
        is attached (the operator asked for visibility), DEBUG otherwise
        (no new log noise for untelemetered runs)."""
        lvl = logging.INFO if self.telemetry is not None else logging.DEBUG
        if _log.isEnabledFor(lvl):
            _log.log(lvl, "%s", self.stats.report(top_n=top_n))

    def _log_param_stats(self, pass_id: int, batch_id: int):
        """Per-parameter scale telemetry (``--show_parameter_stats_period``:
        the reference logs max/avg absolute value per Parameter,
        ``TrainerInternal.cpp:81-92``)."""
        flat = jax.tree_util.tree_flatten_with_path(
            self.train_state.params)[0]
        for path, leaf in flat:
            arr = np.asarray(leaf, np.float32)
            _log.info(
                "param %s shape=%s abs_max=%.4g abs_avg=%.4g mean=%.4g "
                "std=%.4g (pass %d batch %d)",
                jax.tree_util.keystr(path), tuple(arr.shape),
                float(np.abs(arr).max(initial=0)), float(np.abs(arr).mean()),
                float(arr.mean()), float(arr.std()), pass_id, batch_id + 1)

    def evaluate(self, reader: Callable) -> Tuple[float, Dict[str, float]]:
        assert self.train_state is not None
        if self._eval_step is None:
            self._build_eval_step()
        if self.evaluator is not None:
            self.evaluator.reset()
        ts = self.train_state
        costs = []
        with tspan(self.tracer, "eval"):
            for host_batch in reader():
                batch = self._shard(host_batch)
                loss, stats = self._eval_step(ts.params, ts.state, batch)
                costs.append(float(loss))
                if self.evaluator is not None:
                    self.evaluator.update(jax.device_get(stats))
        metrics = self.evaluator.result() if self.evaluator is not None else {}
        return float(np.mean(costs)) if costs else 0.0, metrics

    # -- AOT warmup (ISSUE 16) -----------------------------------------------

    def lower_step(self, sample_batches, rng: Optional[Any] = None):
        """Lower the training step for ``sample_batches``' shapes without
        running it: ``(jax.stages.Lowered, step fingerprint)``. What
        :meth:`warmup` compiles, and how a caller reads the step's text
        (``lowered.as_text()``) or its compiled text
        (``lowered.compile().as_text()``); ``train_state`` is untouched.

        ``sample_batches``: ``steps_per_call * grad_accum`` host batches
        in fused mode (one group of the fused step), one batch (or a
        one-element list) in plain mode. ``rng``: PRNGKey for the
        lowering (default PRNGKey(0))."""
        assert self.train_state is not None, "call init() first"
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        ts = self.train_state
        if self.steps_per_call > 1 or self.grad_accum > 1:
            K, M = self.steps_per_call, self.grad_accum
            if not isinstance(sample_batches, (list, tuple)) \
                    or len(sample_batches) != K * M:
                raise ValueError(
                    f"fused mode needs steps_per_call*grad_accum = "
                    f"{K * M} host batches")
            stacked = self._stack_group(list(sample_batches), K, M)
            if self._fused_step is None:
                self._build_fused_step(stacked)
            batch = self._shard_fused(stacked)
            step_fn = self._fused_step
            fp = _step_fingerprint(stacked)
        else:
            one = (sample_batches[0]
                   if isinstance(sample_batches, (list, tuple))
                   else sample_batches)
            batch = self._shard(one)
            if self._train_step is None:
                self._build_train_step()
            step_fn = self._train_step
            fp = ((1, 1),) + _step_fingerprint(one)
        return step_fn.lower(ts.params, ts.state, ts.opt_state, ts.step,
                             batch, rng), fp

    def warmup(self, sample_batches, rng: Optional[Any] = None
               ) -> Dict[str, Any]:
        """AOT-compile the training step for ``sample_batches``' shapes
        — one ``lower().compile()``, ZERO executions (``train_state``
        and the host step mirror are untouched; executing a step to warm
        it would mutate params). The step fingerprint (PR 2) already
        names exactly what must be cached, so a resume harness warms by
        replaying its known fingerprints' batch shapes through here.

        What the compile buys: with the persistent compilation cache
        configured (:func:`paddle_tpu.obs.xla_cache.setup`) the
        serialized executable lands on
        disk, so THIS process's first real dispatch — and every future
        process resuming the same step — deserializes instead of
        recompiling. With the kernel autotuner enabled, the lowering's
        trace also runs any untuned flash-kernel trials now, off the
        training hot path. Returns ``{fingerprint, wall_s, cache_hit,
        autotune_trials, xla_cache_entries_added}``; emits a
        ``kind="compile"`` record (``meta.warmup=True``) when telemetry
        is attached.

        Args:
          sample_batches: host batches fixing the step's input shapes —
            ``steps_per_call * grad_accum`` batches in fused mode, one
            batch (or a one-element list) in plain mode.
          rng: PRNGKey for the lowering (default PRNGKey(0)).
        """
        from ..nn import autotune
        from ..obs import xla_cache
        entries_before = xla_cache.cache_entry_count()
        trials_before = autotune.stats()["trials"]
        with self._timed(None, "trainer_warmup") as reg:
            lowered, fp = self.lower_step(sample_batches, rng)
            lowered.compile()
        wall = reg.seconds
        added = xla_cache.cache_entry_count() - entries_before
        cache_hit = (None if xla_cache.active_dir() is None
                     else added == 0)
        trials = autotune.stats()["trials"] - trials_before
        if self.telemetry is not None:
            self.telemetry.record_compile(
                fp, wall, cache_hit=cache_hit, autotune_trials=trials,
                meta={"warmup": True, "aot": True})
        return {"fingerprint": fp, "wall_s": round(wall, 6),
                "cache_hit": cache_hit, "autotune_trials": trials,
                "xla_cache_entries_added": added}

    # -- checkpoint ----------------------------------------------------------

    def save(self, checkpoint_dir: str, pass_id: int):
        assert self.train_state is not None
        return ckpt_lib.save_checkpoint(checkpoint_dir, pass_id,
                                        self.train_state.as_dict())

    def restore(self, checkpoint_dir: str, pass_id: Optional[int] = None):
        """Restore from a checkpoint. ``pass_id=None`` loads the newest
        READABLE pass via the fallback chain
        (:func:`~paddle_tpu.train.checkpoint.load_latest_valid`):
        poisoned dirs are quarantined to ``pass-NNNNN.corrupt`` — never
        deleted — and the previous readable pass loads instead
        (``trainer.last_quarantined`` records what was moved aside). An
        explicit ``pass_id`` stays strict and raises on corruption."""
        if pass_id is None:
            loaded = ckpt_lib.load_latest_valid(checkpoint_dir)
        else:
            loaded = ckpt_lib.load_checkpoint(checkpoint_dir, pass_id)
        self.last_quarantined = loaded.pop("_quarantined", [])
        self._last_restored_pass = int(loaded["pass_id"])
        # iterator position (absent in pre-saving_period checkpoints)
        self._last_iter_state = loaded.get("iter")
        put = lambda tree: jax.tree_util.tree_map(jnp.asarray, tree)
        params = put(loaded["params"])
        state = put(loaded.get("state", {}))
        if self._param_sharding is not None:
            # Re-commit the model-parallel layout (checkpoints hold host
            # arrays; without this a resumed run would continue replicated).
            from ..parallel import sharding as shard_lib
            specs = self._param_specs
            if specs is None:
                specs = self._param_sharding
                if isinstance(specs, shard_lib.ShardingRules):
                    specs = specs(params)
                self._param_specs = specs
            params = shard_lib.shard_tree(self.mesh, params, specs)
            state = shard_lib.shard_tree(self.mesh, state)
        # Rebuild optimizer-state pytree type (tuples/namedtuples flattened to
        # plain containers by the npz round-trip) by grafting leaves onto a
        # freshly-built state skeleton — then commit each loaded leaf to the
        # skeleton leaf's (possibly sharded) layout.
        skeleton = self.optimizer.init(params)
        flat_loaded = jax.tree_util.tree_leaves(put(loaded["opt_state"]))
        treedef = jax.tree_util.tree_structure(skeleton)
        opt_state = jax.tree_util.tree_unflatten(treedef, flat_loaded)
        if self._param_sharding is not None:
            opt_state = jax.tree_util.tree_map(
                lambda skel, val: jax.device_put(val, skel.sharding),
                skeleton, opt_state)
        self.train_state = TrainState(*self._commit(
            (params, state, opt_state,
             jnp.asarray(loaded["step"], jnp.int32))))
        return self.train_state
