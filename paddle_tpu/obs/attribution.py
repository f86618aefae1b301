"""Per-scope roofline attribution + exposed-communication estimate over a
parsed HLO module (ISSUE 6 tentpole, part 2).

:mod:`.hloprof` turns the compiled step's HLO text into a structured op
inventory; this module turns the inventory into the two artifacts the
MFU gap needs:

- **The per-scope roofline table.** Every op's FLOPs and buffer bytes
  roll up onto its ``jax.named_scope`` path (the PR-2 scope tree:
  embed / block / attn / ffn / head, tp_attn / sp_allgather / ...), with
  loop-aware execution multipliers, forward/backward split, and a static
  roofline per region: ``est_compute_ms = flops / peak``,
  ``est_memory_ms = bytes / HBM bandwidth``, bound = whichever wins,
  ``idle_ms`` = the time the MXU sits idle while the region is
  memory-bound. The ``mfu_gap_rank`` orders regions by idle time — the
  direct answer to "which region leaves the most hardware idle".
- **The exposed-communication estimate.** Each collective is costed
  against the ICI/DCN bandwidth table and classified by whether it has
  independent compute to hide behind: a BACKWARD collective (the grad
  all-reduce — ``transpose(...)`` in its op metadata) can overlap the
  rest of the backward pass; forward/activation collectives sit on the
  critical path. ``exposed_ms`` charges the non-overlappable time plus
  any overlappable excess beyond the backward-compute budget — the
  measured-not-projected input the all-reduce-overlap ROADMAP item needs.

**Static vs measured.** Everything above is a *static* cost model — the
analytic what-if for the spec-sheet device (on CPU test meshes the
tables substitute ``DEFAULT_DEVICE`` and the report says so via
``bandwidth_assumed``). :func:`parse_profile_trace` is the measured
path: it parses the Chrome-trace JSON a ``Tracer.profile_window()`` /
``jax.profiler`` capture leaves on disk, splits device-lane wall time
into compute vs communication, and interval-subtracts their overlap to
get *measured* exposed-communication time. On real TPU the two sides
join in one report; with no device lanes in the capture (CPU) the
measured block is simply absent — static-only, degrading gracefully.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

from . import hloprof
from .hloprof import (DCN_BYTES_PER_S, DEFAULT_DEVICE, HBM_BANDWIDTH,
                      ICI_BANDWIDTH, ModuleAnalysis)

__all__ = ["build_report", "parse_profile_trace", "format_report"]


def hloprof_grad_sync_scope() -> str:
    """The named-scope root ``parallel.overlap`` traces its explicit
    gradient-sync psums under (imported lazily: obs must not depend on
    the parallel package at import time)."""
    try:
        from ..parallel.overlap import GRAD_SYNC_SCOPE
        return GRAD_SYNC_SCOPE
    except Exception:
        return "grad_sync"

_UNSCOPED = "(unscoped)"

# the serving runtime's named-scope root (TransformerLM.prefill /
# decode_step trace under it): scopes below it are classified as decode
# work, and the report carries a `decode` roofline aggregate — decode is
# memory-bound by construction (one token of compute streams the whole
# parameter set + active KV), and the aggregate's bound says so on the
# same spec-sheet HBM tables as every other row.
DECODE_SCOPE = "decode"


def _scope_key(scope: Tuple[str, ...]) -> str:
    return "/".join(scope) or _UNSCOPED


def build_report(analysis: ModuleAnalysis, *,
                 device_kind: str = "",
                 n_devices: int = 1,
                 cost_analysis_flops: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 hbm_bytes_per_s: Optional[float] = None,
                 ici_bytes_per_s: Optional[float] = None,
                 inter_slice: bool = False,
                 meta: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Build the JSON-safe attribution report from a parsed module.

    Args:
      analysis: :func:`hloprof.parse_module` output for the compiled
        (post-SPMD, per-device) HLO.
      device_kind: ``jax.devices()[0].device_kind`` — keys the bandwidth
        and peak-FLOPs tables; unknown kinds substitute
        ``DEFAULT_DEVICE`` and set ``bandwidth_assumed`` — off-TPU only
        (the CPU what-if); an unknown TPU kind raises.
      n_devices: mesh size (the default replica-group size for
        collectives whose groups aren't printed explicitly).
      cost_analysis_flops: ``compiled.cost_analysis()['flops']`` when the
        backend provides it — recorded alongside the parsed static total
        with the agreement percentage (the bench smoke gate pins <= 5%).
      inter_slice: cost collectives at DCN instead of ICI bandwidth.
      meta: extra fields merged into the report (mesh axes, K/M, ...).
    """
    from .telemetry import PEAK_FLOPS      # lazy: telemetry imports jax

    assumed = False
    peak = peak_flops if peak_flops is not None \
        else PEAK_FLOPS.get(device_kind)
    hbm = hbm_bytes_per_s if hbm_bytes_per_s is not None \
        else HBM_BANDWIDTH.get(device_kind)
    ici = ici_bytes_per_s if ici_bytes_per_s is not None \
        else ICI_BANDWIDTH.get(device_kind)
    if peak is None or hbm is None or ici is None:
        if device_kind.startswith("TPU"):
            # a real chip is never modelled by another chip's numbers
            raise KeyError(
                f"no peak FLOP/s or bandwidth entry for device kind "
                f"{device_kind!r}: add it to obs.telemetry.PEAK_FLOPS and "
                f"obs.hloprof's HBM/ICI tables")
        assumed = True
        peak = peak if peak is not None else PEAK_FLOPS[DEFAULT_DEVICE]
        hbm = hbm if hbm is not None else HBM_BANDWIDTH[DEFAULT_DEVICE]
        ici = ici if ici is not None else ICI_BANDWIDTH[DEFAULT_DEVICE]
    comm_bw = DCN_BYTES_PER_S if inter_slice else ici

    # -- per-scope rollup ---------------------------------------------------
    by_scope: Dict[str, Dict[str, float]] = {}
    rollup: Dict[str, float] = {}        # every scope-path prefix -> flops
    for op in analysis.ops:
        key = _scope_key(op.scope)
        e = by_scope.setdefault(key, {
            "flops": 0.0, "flops_static": 0.0, "bytes": 0.0,
            "fwd_flops": 0.0, "bwd_flops": 0.0, "ops": 0})
        e["ops"] += 1
        if op.flops:
            e["flops"] += op.flops * op.multiplier
            e["flops_static"] += op.flops
            side = "bwd_flops" if op.backward else "fwd_flops"
            e[side] += op.flops * op.multiplier
            for i in range(1, len(op.scope) + 1):
                pref = "/".join(op.scope[:i])
                rollup[pref] = rollup.get(pref, 0.0) \
                    + op.flops * op.multiplier
            if not op.scope:
                rollup[_UNSCOPED] = rollup.get(_UNSCOPED, 0.0) \
                    + op.flops * op.multiplier
        if not op.fusion_internal:
            # fusion-boundary bytes only: fusion internals live in
            # registers/VMEM, so counting them would inflate the
            # memory-traffic proxy the roofline divides by
            e["bytes"] += op.bytes * op.multiplier

    flops_total = analysis.flops_loop_aware()
    flops_static = analysis.flops_static()

    scopes = []
    for key, e in by_scope.items():
        compute_ms = e["flops"] / peak * 1e3
        memory_ms = e["bytes"] / hbm * 1e3
        est_ms = max(compute_ms, memory_ms)
        scopes.append({
            "scope": key,
            "region": DECODE_SCOPE if (key == DECODE_SCOPE or
                                       key.startswith(DECODE_SCOPE + "/"))
            else None,
            "flops": round(e["flops"]),
            "flops_static": round(e["flops_static"]),
            "flops_frac": round(e["flops"] / flops_total, 4)
            if flops_total else 0.0,
            "bytes": round(e["bytes"]),
            "intensity_flops_per_byte": round(e["flops"] / e["bytes"], 3)
            if e["bytes"] else None,
            "est_compute_ms": round(compute_ms, 6),
            "est_memory_ms": round(memory_ms, 6),
            "est_ms": round(est_ms, 6),
            "bound": ("compute" if compute_ms >= memory_ms else "memory")
            if est_ms else "none",
            "idle_ms": round(max(est_ms - compute_ms, 0.0), 6),
            "idle_frac": round(1.0 - compute_ms / est_ms, 4)
            if est_ms else 0.0,
            "bwd_frac": round(e["bwd_flops"] / e["flops"], 4)
            if e["flops"] else 0.0,
            "ops": e["ops"],
        })
    scopes.sort(key=lambda s: -s["flops"])
    mfu_gap_rank = sorted(
        (s for s in scopes if s["est_ms"] > 0),
        key=lambda s: -s["idle_ms"])
    mfu_gap_rank = [{"scope": s["scope"], "idle_ms": s["idle_ms"],
                     "idle_frac": s["idle_frac"], "bound": s["bound"],
                     "est_ms": s["est_ms"]} for s in mfu_gap_rank]

    # -- collectives: exposed vs overlappable -------------------------------
    inventory = hloprof.collective_inventory(analysis,
                                             default_group=n_devices)
    bwd_compute_ms = sum(op.flops * op.multiplier
                         for op in analysis.ops if op.backward) / peak * 1e3
    collectives = []
    total_wire = exposed_base_ms = overlappable_ms = 0.0
    grad_ar_wire = grad_ar_count = 0
    grad_rows = []
    gs_scope = hloprof_grad_sync_scope()
    decode_rows_comm = []
    for c in inventory:
        wire_total = c.wire_bytes * c.multiplier
        t_comm_ms = wire_total / comm_bw * 1e3
        # a backward collective (the grad sync autodiff's transpose
        # emits) has the REST of the backward pass as independent
        # compute to hide behind; forward/activation collectives feed
        # the very next op — critical path. Explicit grad-sync psums
        # (parallel.overlap) are recognized by their named scope too:
        # the accumulated-gradient sync is traced OUTSIDE the transpose
        # (no backward metadata) but is still the gradient collective.
        is_grad_sync = bool(c.scope) and c.scope[0] == gs_scope
        # a collective under a decode/* scope is SERVING communication
        # (ISSUE 15: the tp-sharded tick's out-proj/ffn all-reduces and
        # any AG/RS the partitioner derives) — classified into the
        # serving comm table below instead of falling through unlabeled
        is_decode = bool(c.scope) and c.scope[0] == DECODE_SCOPE
        overlappable = c.backward or is_grad_sync
        d = c.to_dict()
        d.update({
            "wire_bytes_total": round(wire_total),
            "t_comm_ms": round(t_comm_ms, 6),
            "overlappable": overlappable,
            "region": DECODE_SCOPE if is_decode else None,
        })
        collectives.append(d)
        if is_decode:
            decode_rows_comm.append(d)
        total_wire += wire_total
        if overlappable:
            overlappable_ms += t_comm_ms
        else:
            exposed_base_ms += t_comm_ms
        if c.kind == "all-reduce" and (c.backward or is_grad_sync):
            grad_ar_wire += wire_total
            grad_ar_count += 1
            if is_grad_sync:
                # one row per explicit sync bucket (ISSUE 8: the
                # per-bucket comm table the smoke gate asserts)
                grad_rows.append({
                    "scope": d["scope"],
                    "payload_bytes": c.payload_bytes,
                    "wire_bytes_total": round(wire_total),
                    "t_comm_ms": round(t_comm_ms, 6),
                    "multiplier": c.multiplier,
                    "is_async": c.is_async,
                    "sched_distance": c.sched_distance,
                })
    hidden_ms = min(overlappable_ms, bwd_compute_ms)
    exposed_ms = exposed_base_ms + (overlappable_ms - hidden_ms)
    grad_ar_ms = grad_ar_wire / comm_bw * 1e3
    comm = {
        "total_wire_bytes_per_device": round(total_wire),
        "t_comm_ms": round(exposed_base_ms + overlappable_ms, 6),
        "overlappable_ms": round(overlappable_ms, 6),
        "exposed_ms": round(exposed_ms, 6),
        "backward_compute_budget_ms": round(bwd_compute_ms, 6),
        "link": "DCN" if inter_slice else "ICI",
        "bytes_per_s": comm_bw,
        "grad_allreduce": {
            "ops": grad_ar_count,
            "wire_bytes_per_device": round(grad_ar_wire),
            "t_comm_ms": round(grad_ar_ms, 6),
            # what stays exposed if the grad sync overlaps the backward
            # pass (the ROADMAP all-reduce-overlap item's target number)
            "exposed_ms_if_overlapped": round(
                max(0.0, grad_ar_ms - bwd_compute_ms), 6),
            "exposed_ms_today": round(grad_ar_ms, 6),
            "hides_under_backward": bool(grad_ar_ms <= bwd_compute_ms),
            # per-bucket rows of an explicit (parallel.overlap) sync —
            # empty under the implicit partitioner sync
            "buckets": grad_rows,
        } if grad_ar_count else None,
    }

    # -- decode aggregate (serving programs only) ---------------------------
    decode_rows = [s for s in scopes if s["region"] == DECODE_SCOPE]
    decode = None
    if decode_rows:
        d_flops = sum(s["flops"] for s in decode_rows)
        d_bytes = sum(s["bytes"] for s in decode_rows)
        d_comp = d_flops / peak * 1e3
        d_mem = d_bytes / hbm * 1e3
        decode = {
            "flops": round(d_flops),
            "bytes": round(d_bytes),
            "est_compute_ms": round(d_comp, 6),
            "est_memory_ms": round(d_mem, 6),
            "bound": ("compute" if d_comp >= d_mem else "memory")
            if (d_comp or d_mem) else "none",
            "intensity_flops_per_byte": round(d_flops / d_bytes, 3)
            if d_bytes else None,
            "scopes": len(decode_rows),
        }
        if decode_rows_comm:
            # the serving comm table (ISSUE 15): tensor-parallel
            # collectives the sharded tick pays per dispatch — on the
            # tick's critical path (no backward to hide behind), so
            # their wire time adds directly to per-token latency
            decode["comm"] = {
                "ops": len(decode_rows_comm),
                "kinds": {},
                "wire_bytes_total": round(sum(
                    r["wire_bytes_total"] for r in decode_rows_comm)),
                "t_comm_ms": round(sum(
                    r["t_comm_ms"] for r in decode_rows_comm), 6),
                "collectives": [
                    {"kind": r["kind"], "scope": r["scope"],
                     "payload_bytes": r["payload_bytes"],
                     "wire_bytes_total": r["wire_bytes_total"],
                     "t_comm_ms": r["t_comm_ms"],
                     "multiplier": r["multiplier"]}
                    for r in decode_rows_comm],
            }
            for r in decode_rows_comm:
                k = decode["comm"]["kinds"]
                k[r["kind"]] = k.get(r["kind"], 0) + 1

    # -- headline ------------------------------------------------------------
    compute_ms = flops_total / peak * 1e3
    memory_ms = sum(e["bytes"] for e in by_scope.values()) / hbm * 1e3
    est_step_ms = max(compute_ms, memory_ms) + exposed_ms
    agreement = None
    if cost_analysis_flops:
        agreement = round(
            100.0 * (flops_static - cost_analysis_flops)
            / cost_analysis_flops, 3)
    report = {
        "kind": "attribution",
        "device_kind": device_kind or None,
        "model_device": DEFAULT_DEVICE if assumed else device_kind,
        "bandwidth_assumed": assumed,
        "n_devices": n_devices,
        "peak_flops": peak,
        "hbm_bytes_per_s": hbm,
        "flops_total": round(flops_total),
        "flops_static": round(flops_static),
        "cost_analysis_flops": cost_analysis_flops,
        "flops_vs_cost_analysis_pct": agreement,
        "unknown_trip_loops": analysis.unknown_trip_loops,
        "est_compute_ms": round(compute_ms, 6),
        "est_memory_ms": round(memory_ms, 6),
        "est_step_ms": round(est_step_ms, 6),
        "est_mfu_pct": round(100.0 * compute_ms / est_step_ms, 2)
        if est_step_ms else None,
        "scopes": scopes,
        "scope_rollup": {k: round(v) for k, v in sorted(rollup.items())},
        "mfu_gap_rank": mfu_gap_rank,
        "decode": decode,
        "collectives": collectives,
        "comm": comm,
    }
    if meta:
        report.update(meta)
    return report


# ---------------------------------------------------------------------------
# measured path: device lanes of a jax.profiler Chrome-trace capture
# ---------------------------------------------------------------------------

_COMM_NAME_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"\bsend\b|\brecv\b|\bnccl", re.I)
_DEVICE_PROC_RE = re.compile(r"TPU|/device:|GPU", re.I)


def _merge_intervals(iv: List[Tuple[float, float]]
                     ) -> List[Tuple[float, float]]:
    iv = sorted(iv)
    out: List[Tuple[float, float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _interval_overlap(a: List[Tuple[float, float]],
                      b: List[Tuple[float, float]]) -> float:
    """Total overlap between two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def parse_profile_trace(log_dir: str) -> Optional[Dict[str, Any]]:
    """Parse the Chrome-trace JSON of a ``jax.profiler`` capture under
    ``log_dir`` (the ``Tracer.profile_window()`` output tree) into
    measured device compute-vs-communication wall time.

    Returns None when no trace file or no device lanes exist (a CPU
    capture) — the caller degrades to the static report. Collective ops
    are recognized by name on the device lanes; exposed communication is
    the comm wall minus its interval-overlap with compute."""
    paths = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                  recursive=True)
        + glob.glob(os.path.join(log_dir, "**", "*.trace.json"),
                    recursive=True))
    if not paths:
        return None
    path = paths[-1]                    # most recent capture wins
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt") as f:
                data = json.load(f)
        else:
            with open(path) as f:
                data = json.load(f)
    except (OSError, json.JSONDecodeError, EOFError):
        return None
    events = data.get("traceEvents", [])
    device_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            name = (e.get("args") or {}).get("name", "")
            if _DEVICE_PROC_RE.search(name):
                device_pids.add(e.get("pid"))
    if not device_pids:
        return None
    comm_iv: List[Tuple[float, float]] = []
    comp_iv: List[Tuple[float, float]] = []
    comm_us = comp_us = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        ts, dur = e.get("ts"), e.get("dur")
        if ts is None or dur is None or dur <= 0:
            continue
        if _COMM_NAME_RE.search(e.get("name", "")):
            comm_iv.append((ts, ts + dur))
            comm_us += dur
        else:
            comp_iv.append((ts, ts + dur))
            comp_us += dur
    if not comm_iv and not comp_iv:
        return None
    comm_m, comp_m = _merge_intervals(comm_iv), _merge_intervals(comp_iv)
    overlap_us = _interval_overlap(comm_m, comp_m)
    comm_union = sum(e - s for s, e in comm_m)
    all_iv = _merge_intervals(comm_iv + comp_iv)
    wall_us = (all_iv[-1][1] - all_iv[0][0]) if all_iv else 0.0
    return {
        "source": path,
        "device_lanes": len(device_pids),
        "device_compute_ms": round(comp_us / 1e3, 3),
        "device_comm_ms": round(comm_us / 1e3, 3),
        "exposed_comm_ms": round((comm_union - overlap_us) / 1e3, 3),
        "comm_overlap_frac": round(overlap_us / comm_union, 4)
        if comm_union else None,
        "device_wall_ms": round(wall_us / 1e3, 3),
    }


# ---------------------------------------------------------------------------
# human-readable rendering (the report CLI and notebooks share this)
# ---------------------------------------------------------------------------

def format_report(report: Dict[str, Any], top_n: int = 12) -> str:
    """Compact fixed-width rendering of an attribution report."""
    lines = []
    dev = report.get("model_device") or "?"
    lines.append(
        f"attribution ({'assumed ' if report.get('bandwidth_assumed') else ''}"
        f"{dev}, {report.get('n_devices')} dev): "
        f"{report.get('flops_total'):.3e} FLOPs/step, "
        f"est {report.get('est_step_ms'):.3f} ms, "
        f"est MFU {report.get('est_mfu_pct')}%")
    lines.append(f"{'scope':<34}{'GFLOPs':>10}{'frac':>7}{'MB':>9}"
                 f"{'bound':>8}{'idle_ms':>9}")
    for s in report.get("scopes", [])[:top_n]:
        lines.append(
            f"{s['scope'][:33]:<34}{s['flops'] / 1e9:>10.3f}"
            f"{s['flops_frac']:>7.2%}{s['bytes'] / 1e6:>9.2f}"
            f"{s['bound']:>8}{s['idle_ms']:>9.4f}")
    comm = report.get("comm") or {}
    lines.append(
        f"comm: {comm.get('total_wire_bytes_per_device', 0) / 1e6:.2f} MB "
        f"wire/dev over {comm.get('link')}, "
        f"{comm.get('t_comm_ms', 0):.3f} ms total, "
        f"{comm.get('exposed_ms', 0):.3f} ms exposed "
        f"({comm.get('overlappable_ms', 0):.3f} ms overlappable vs "
        f"{comm.get('backward_compute_budget_ms', 0):.3f} ms bwd budget)")
    gar = comm.get("grad_allreduce")
    if gar:
        lines.append(
            f"grad all-reduce: {gar['ops']} ops, "
            f"{gar['wire_bytes_per_device'] / 1e6:.2f} MB/dev, "
            f"{gar['t_comm_ms']:.3f} ms exposed today, "
            f"{gar['exposed_ms_if_overlapped']:.3f} ms if overlapped with "
            f"backward (hides: {gar['hides_under_backward']})")
        for row in gar.get("buckets") or []:
            sd = row.get("sched_distance")
            lines.append(
                f"  {row['scope']:<32}{row['payload_bytes'] / 1e6:>8.2f} MB"
                f"{row['t_comm_ms']:>10.4f} ms  x{row['multiplier']:g}"
                f"  sched_distance={'-' if sd is None else sd}")
    dec_comm = (report.get("decode") or {}).get("comm")
    if dec_comm:
        kinds = ", ".join(f"{k} x{v}" for k, v in
                          sorted(dec_comm.get("kinds", {}).items()))
        lines.append(
            f"decode tp comm: {dec_comm['ops']} ops ({kinds}), "
            f"{dec_comm['wire_bytes_total'] / 1e6:.3f} MB wire/dev, "
            f"{dec_comm['t_comm_ms']:.4f} ms per tick (critical path)")
    measured = report.get("measured")
    if measured:
        lines.append(
            f"measured: compute {measured['device_compute_ms']} ms, comm "
            f"{measured['device_comm_ms']} ms, exposed "
            f"{measured['exposed_comm_ms']} ms "
            f"(overlap {measured['comm_overlap_frac']})")
    return "\n".join(lines)
