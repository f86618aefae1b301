"""The benchmark's harness: finds a cell, its configuration, its traffic
mix and its per-layer metrics BY NAME from ``BENCHMARK.json``, hands them
to the cell's driver, and turns what the driver measured into the one
JSON line the contract asks for.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a file of its own:

- ``BENCHMARK.json`` ``configs[].file``         the sizes, as run, and the
  paths of the three modules that know the ARCHITECTURE (below)
- ``benchmarks/traffic/<traffic>.json``         the mix's parameters
- ``benchmarks/workloads/<cell>.json``          driver, sizing, limits
- ``benchmarks/layer_metrics/<metric>.py``      ``read(ctx)``
- ``benchmarks/drivers/<driver>.py``            ``run(ctx)``

so a later PR adds a cell, a configuration or a per-layer metric with new
files and new ``BENCHMARK.json`` entries only.

Of a configuration the harness, the drivers, the traffic generator and the
readers know its NAME and the modules its file names, by path, under three
keys. Whatever depends on the architecture lives in those:

- ``reference``  ``dims(cfg)`` (hashable, read from the file's own keys; the
  one attribute others may read is ``V``, the vocabulary held here),
  ``serve_reference(cfg, seed, sequences, quant=None)``,
  ``train_reference(cfg, seed, batches, opt, quant=None, fault=None)``
- ``layout``     the program's side: ``build_model(z)``, ``loss_fn(z)``,
  ``program_params(z, seed)`` and ``seed_params(z, seed)`` (the same tree
  inside a jit), ``flatten_reference`` / ``flatten_program`` (per-leaf
  readings of both sides under common keys), ``engine_facts(engine)``
  (what only this architecture's readers use of the built engine)
- ``costs``      model FLOPs from shapes: ``train_flops_per_token(z,
  seq_len)``, ``serve_flops(z, prompts, contexts)``, and
  ``attention_shape(z)`` (layers, heads, head size) for the kernel readers

A configuration of a NEW architecture therefore comes as new files and
appended entries only: its configuration file, its three modules, its cell
and traffic files, the readers of the per-layer metrics it adds, its
``BENCHMARK.json`` entries, and its cell's name appended to the
``workloads`` lists of the end-to-end and per-layer metrics it reports.
Nothing else is touched (``tests/benchmark`` does it with a toy).
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")      # git-ignored, removed
# the keys under which a configuration's file names its architecture's modules
ARCHITECTURE = ("reference", "layout", "costs")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of ALL the values given."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def load_peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's peaks; a kind with no entry is an error, not a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"benchmarks/peaks.json ({sorted(table)})")
    return table[device_kind]


class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with every file
    that belongs to it, found by name."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"({sorted(entries)})")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(os.path.join(
            root, configs[self.entry["config"]]["file"]))
        bdir = os.path.join(root, "benchmarks")
        self.traffic = load_json(os.path.join(
            bdir, "traffic", self.entry["traffic"] + ".json"))
        self.file = load_json(os.path.join(bdir, "workloads",
                                           name + ".json"))
        self.driver = self.file["driver"]
        missing = [k for k in ARCHITECTURE if k not in self.config]
        if missing:
            raise KeyError(f"configuration {self.entry['config']!r} names "
                           f"no {missing} module")
        self.reference, self.layout, self.costs = (
            load_module(self.config[key], root) for key in ARCHITECTURE)

    def _reports(self, metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    @property
    def per_layer(self) -> List[Dict[str, Any]]:
        """This cell's per-layer metrics: those that list it, and those
        with no list whose end-to-end metric this cell reports."""
        mine = {m["name"] for m in self.end_to_end}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def load_module(path: str, root: str = ROOT):
    """The module a data file names by its path in the checkout: looked up
    under ``root`` and, if it is not there, under the harness's own
    checkout (a toy root brings modules of its own and still names
    ``benchmarks/reference.py``)."""
    for base in (root, ROOT):
        full = os.path.join(base, path)
        if os.path.isfile(full):
            break
    else:
        raise FileNotFoundError(f"no module {path!r} under {root}"
                                + ("" if root == ROOT else f" or {ROOT}"))
    name = os.path.splitext(path)[0].replace(".", "_").replace("/", ".")
    spec = importlib.util.spec_from_file_location(name, full)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: str = ROOT) -> Callable:
    """``benchmarks/layer_metrics/<metric>.py``'s ``read``."""
    return load_module(os.path.join("benchmarks", "layer_metrics",
                                    metric + ".py"), root).read


class Recorder:
    """Host spans, kept in memory. A span is also written
    into the profiler's trace (``bench:<name>``) while one is being
    taken, so that idle device time can be given to what the host did."""

    def __init__(self):
        self.spans: Dict[str, List[Tuple[float, float, Dict]]] = {}
        self.tracing = False
        self._open: Dict[str, Any] = {}

    @contextlib.contextmanager
    def span(self, name: str, **facts):
        ann = self._annotation(name)
        t0 = time.perf_counter()
        try:
            yield facts
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            facts["traced"] = ann is not None
            self.spans.setdefault(name, []).append((t0, t1, facts))

    def _annotation(self, name: str):
        if not self.tracing:
            return None
        import jax
        ann = jax.profiler.TraceAnnotation("bench:" + name)
        ann.__enter__()
        return ann

    def begin(self, name: str, **facts) -> None:
        """Open a span that a later :meth:`end` closes (for a region that
        starts in one callback and ends in another, on one thread)."""
        self._open[name] = (time.perf_counter(), self._annotation(name),
                            facts)

    def end(self, name: str) -> None:
        if name not in self._open:
            return
        t0, ann, facts = self._open.pop(name)
        t1 = time.perf_counter()
        if ann is not None:
            ann.__exit__(None, None, None)
        facts["traced"] = ann is not None
        self.spans.setdefault(name, []).append((t0, t1, facts))

    def durations_ms(self, name: str, lo: float = float("-inf"),
                     hi: float = float("inf")) -> List[float]:
        return [(t1 - t0) * 1e3 for t0, t1, _ in self.spans.get(name, ())
                if t0 >= lo and t1 <= hi]

    def traced(self, name: str) -> List[Tuple[float, float, Dict]]:
        return [s for s in self.spans.get(name, ()) if s[2].get("traced")]


class Profile:
    """One ``jax.profiler`` trace of a sub-window, into a git-ignored
    directory inside the checkout, removed once it is reduced. The driver
    calls :meth:`start` at a step boundary and :meth:`tick` at every later
    one: the first tick opens the traced window (the step in between lets
    the profiler attach; one run in five stalled 2 s there), a tick
    ``seconds`` later closes it and stops the profiler."""

    def __init__(self, rec: Recorder, enabled: bool, seconds: float):
        self.rec = rec
        self.enabled = enabled
        self.seconds = seconds
        self.started = False
        self.opened_at: Optional[float] = None
        self.done = False

    def start(self) -> None:
        if not self.enabled or self.started:
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.started = True

    def tick(self) -> None:
        if not self.started or self.done:
            return
        if self.opened_at is None:
            self.rec.tracing = True
            self.rec.begin("window")
            self.opened_at = time.perf_counter()
        elif time.perf_counter() - self.opened_at >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if not self.started or self.done:
            return
        import jax
        self.rec.end("window")
        self.rec.tracing = False
        jax.profiler.stop_trace()
        self.done = True


class Context:
    """What a driver gets, and what a per-layer reader reads."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, device: Dict[str, Any],
                 peaks: Dict[str, Any]):
        from benchmarks import costs
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace_on = bool(trace)
        self.t_start = t_start
        self.device = device
        self.peaks = peaks
        self.costs = costs      # kernels' and chip's (cell.costs: the model's)
        self.dims = cell.reference.dims(cell.config)
        self.rec = Recorder()
        self.profile = Profile(self.rec, trace, float(
            cell.file.get("trace_seconds", 3.0)))
        self.window: Tuple[float, float] = (0.0, 0.0)
        self.facts: Dict[str, Any] = {}
        self.trace: Optional[Dict[str, Any]] = None
        self.kernels: List[str] = list(cell.file.get("kernels", ()))

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, name: str) -> List[float]:
        return self.rec.durations_ms(name, *self.window)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend reports
    none, as the CPU does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def free_device_memory() -> None:
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def check(name: str, value: float, limit: float) -> Dict[str, Any]:
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}


def make_context(cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, devices, peaks: Dict[str, Any]) -> Context:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    ctx = Context(cell, seed, seconds, trace, t_start, device, peaks)
    ctx.devices = list(devices)
    return ctx


def load_driver(cell: Cell):
    return importlib.import_module("benchmarks.drivers." + cell.driver)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, peaks: Dict[str, Any],
             xplane: Optional[str] = None) -> Dict[str, Any]:
    """Drive one cell and build the result line. ``devices`` are the
    chips the cell runs on, as JAX reports them. ``xplane``: a recorded
    trace to reduce in place of the one just taken (the CPU's profiler
    writes no device plane, so the tests hand over a recorded one)."""
    ctx = make_context(cell, seed, seconds, trace, t_start, devices, peaks)
    out = load_driver(cell).run(ctx)   # set-up, window, peak, free, reference
    return finish(ctx, out, xplane)


def finish(ctx: Context, out: Dict[str, Any],
           xplane: Optional[str] = None) -> Dict[str, Any]:
    """From what a driver measured to the contract's result line."""
    cell, device, trace, t_start = (ctx.cell, ctx.device, ctx.trace_on,
                                    ctx.t_start)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    e2e = dict(out["metrics"])
    e2e["setup_s"] = ctx.window[0] - t_start
    result: Dict[str, Any] = {
        "correct": bool(out["correct"]), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": {}, "device": device}
    if trace:
        from benchmarks import trace_reduce
        ctx.profile.stop()
        ctx.trace = trace_reduce.reduce_trace(
            xplane or trace_reduce.find_xplane(TRACE_DIR), ctx.kernels)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"trace: window {ctx.trace['window_s']:.3f}s, busy "
            f"{ctx.trace['busy_s']:.3f}s on {ctx.trace['n_devices']} "
            f"device(s); kernels {ctx.trace['kernel_seconds']} calls "
            f"{ctx.trace['kernel_calls']}")
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
        ctx.end_to_end = e2e
        for m in cell.per_layer:
            value = load_reader(m["name"], cell.root)(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": float(e2e[m["name"]]),
                                            "unit": m["unit"]}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in out["checks"]}
    return result


def emit(result: Dict[str, Any]) -> None:
    """Each number compared beside its limit as the last lines on
    standard error, then the result as the last line on standard output."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        log(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g}) "
            f"{verdict}")
    log(f"correct: {result['correct']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
