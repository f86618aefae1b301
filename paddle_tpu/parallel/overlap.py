"""Bucketed gradient-sync overlap: hide the dp all-reduce under the backward.

The reference Paddle's entire pserver tier existed to pipeline gradient
communication against computation — parameters were split into blocks,
each block's gradient shipped to its server the moment the backward
produced it (``pserver/ParameterServer2.h:73``, SURVEY §3.3). The
XLA-era default collapses all of that into GSPMD: the partitioner
inserts one all-reduce per gradient tensor wherever the backward
computes it, then the backend's combiner/scheduler typically merges and
sinks them into one monolithic sync after the full backward, all of it
exposed.

This module makes the sync OURS again, pserver-style but on-device:

- **Buckets** (:func:`partition_buckets`): parameter leaves are grouped
  in *reverse layer order* (output side first — the order the backward
  completes them) into byte-budgeted, dtype-homogeneous buckets.
- **The marker** (:func:`sync_tangent`): a ``custom_vjp`` identity
  wrapped around each bucket's leaves. Forward: nothing. Backward: the
  bucket's cotangents are raveled into ONE flat buffer and ``psum``-ed
  over the dp axis the moment the bucket's backward slice completes —
  one all-reduce per bucket, anchored *inside* the backward where the
  scheduler can float it under the remaining backward compute, instead
  of one giant post-backward sync.
- **The manual-dp region**: explicit ``lax.psum`` needs a bound axis
  name, so the Trainer runs the forward+backward of each microbatch
  inside a ``shard_map`` over the dp axis (other mesh axes stay ``auto``
  — GSPMD keeps partitioning tensor-parallel math; the Megatron
  composition). Inside, each device differentiates its LOCAL loss sum;
  the markers' psums are the only dp gradient communication in the
  program. ``grad_sync="fused"`` is the same machinery with a single
  bucket — the one-big-all-reduce baseline the HLO gate compares
  against (fused = 1 grad all-reduce, bucketed >= 2).
- **The in-scan path** (:func:`sync_scan_slice`): a remat
  scan-over-layers stack accumulates its stacked-leaf gradient across
  the *whole* scan transpose — a top-level bucket marker on those
  leaves could not fire until the last layer. The model hooks the
  marker onto the per-layer parameter slice INSIDE the scan body
  (``TransformerLM._scan_blocks``), so each layer's slice is all-reduced
  within its own backward iteration. Activated by the Trainer through
  :func:`scan_sync_scope`; a no-op everywhere else (init, eval, implicit
  mode).

Numerics: all-reduce is an elementwise sum over the same replica group,
so bucket granularity does not change any element's reduction — bucketed
and fused are bit-exact in f32 (pinned by tests/test_overlap.py on a
2-device mesh). With ``grad_accum > 1`` the Trainer accumulates LOCAL
gradients across microbatches and syncs the accumulated tree once per
optimizer step (:func:`apply_bucket_sync`) — never per microbatch.

Known semantic deltas vs the implicit GSPMD path (documented, not bugs):
module-state updates (BN running stats) and dropout masks are computed
per device shard inside the manual region — torch-DDP semantics rather
than global-batch semantics. The Trainer warns once when a non-empty
state tree meets an explicit sync mode.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import functools
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = [
    "GRAD_SYNC_SCOPE", "GRAD_SYNC_MODES", "Bucket",
    "partition_buckets", "sync_tangent", "mark_buckets",
    "apply_bucket_sync", "scan_sync_scope", "current_scan_sync",
    "sync_scan_slice", "resolve_grad_sync",
]

# Every explicit-sync psum is traced under this jax.named_scope, so the
# program's all-reduces carry a grad_sync/<tag> location: a trace names
# them by it, and tests/test_overlap.py counts them per mode.
GRAD_SYNC_SCOPE = "grad_sync"

GRAD_SYNC_MODES = (None, "bucketed", "fused")


# ---------------------------------------------------------------------------
# bucket partition
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Bucket:
    """One gradient-sync bucket: a tag (its HLO scope suffix), the leaf
    paths it covers (slash-joined, reverse layer order), and its size."""
    tag: str
    paths: Tuple[str, ...]
    bytes: int
    dtype: str

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self) | {"paths": list(self.paths)}


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _leaf_entries(params) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return [(_path_str(path), leaf) for path, leaf in flat]


def partition_buckets(params, bucket_mb: float = 4.0,
                      exclude: Sequence[str] = ()) -> List[Bucket]:
    """Partition a params tree's leaves into gradient-sync buckets.

    Leaves are taken in REVERSE flatten order — the flatten order follows
    module construction (input side first), so reversing approximates the
    order the backward pass completes gradients: the first bucket closes
    (and its all-reduce can start) earliest. Consecutive leaves of one
    dtype are grouped until the bucket exceeds ``bucket_mb`` megabytes
    (a dtype change always cuts: each bucket concatenates into one flat
    psum buffer). ``exclude`` is a list of fnmatch patterns over
    slash-joined leaf paths — the leaves a model syncs in-scan
    (:func:`sync_scan_slice`) must not be double-synced by a bucket.

    Non-inexact leaves (no cotangent) are skipped. Every bucket holds at
    least one leaf, however large the leaf; ``bucket_mb`` is a budget,
    not a splitter (a single tensor is never sliced across buckets —
    slicing would forfeit the "fires when its producers finish" anchor).
    """
    if bucket_mb <= 0:
        raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
    budget = int(bucket_mb * 2 ** 20)
    entries = _leaf_entries(params)
    buckets: List[Bucket] = []
    cur: List[Tuple[str, Any]] = []
    cur_bytes = 0

    def close():
        nonlocal cur, cur_bytes
        if cur:
            dt = str(np.dtype(cur[0][1].dtype))
            buckets.append(Bucket(
                tag=f"bucket{len(buckets)}",
                paths=tuple(p for p, _ in cur),
                bytes=cur_bytes, dtype=dt))
            cur, cur_bytes = [], 0

    for path, leaf in reversed(entries):
        dt = getattr(leaf, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.inexact):
            continue
        if any(fnmatch.fnmatchcase(path, pat) for pat in exclude):
            continue
        nbytes = int(np.prod(np.shape(leaf), dtype=np.int64)) * \
            np.dtype(dt).itemsize
        if cur and (str(np.dtype(cur[0][1].dtype)) != str(np.dtype(dt))
                    or cur_bytes + nbytes > budget):
            close()
        cur.append((path, leaf))
        cur_bytes += nbytes
    close()
    return buckets


# ---------------------------------------------------------------------------
# the custom_vjp identity marker
# ---------------------------------------------------------------------------

def _flat_psum(gs: Tuple[Any, ...], axis_name, tag: str) -> Tuple[Any, ...]:
    """All-reduce a tuple of same-dtype cotangents as ONE flat buffer:
    ravel + concatenate, a single ``lax.psum`` (one HLO all-reduce — the
    per-leaf form would emit one op per leaf and hand the backend the
    same fragmented schedule we are replacing), then slice/reshape back.
    Traced under ``named_scope(grad_sync/<tag>)``."""
    with jax.named_scope(f"{GRAD_SYNC_SCOPE}/{tag}"):
        if len(gs) == 1:
            g = gs[0]
            return (lax.psum(g, axis_name),)
        flat = [jnp.ravel(g) for g in gs]
        buf = lax.psum(jnp.concatenate(flat), axis_name)
        outs, off = [], 0
        for g, f in zip(gs, flat):
            n = int(f.shape[0])
            outs.append(jnp.reshape(lax.slice(buf, (off,), (off + n,)),
                                    jnp.shape(g)))
            off += n
        return tuple(outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sync_tangent(xs: Tuple[Any, ...], axis_name, tag: str = "bucket"):
    """Identity on a tuple of arrays whose COTANGENTS are all-reduced over
    ``axis_name`` (one flat psum per call) the moment the backward has
    produced all of them. Must be traced where ``axis_name`` is bound —
    inside the Trainer's manual-dp ``shard_map`` region."""
    return xs


def _sync_fwd(xs, axis_name, tag):
    return xs, None


def _sync_bwd(axis_name, tag, _res, gs):
    return (_flat_psum(tuple(gs), axis_name, tag),)


sync_tangent.defvjp(_sync_fwd, _sync_bwd)


def mark_buckets(params, buckets: Sequence[Bucket], axis_name):
    """Wrap each bucket's leaves in one :func:`sync_tangent` marker;
    returns the same tree with marked leaves (unbucketed leaves pass
    through untouched). Applied to the params at the top of the loss
    function, so the markers' backward psums fire as the backward
    completes each bucket."""
    flat = jax.tree_util.tree_flatten_with_path(params)
    leaves = {_path_str(path): leaf for path, leaf in flat[0]}
    for b in buckets:
        marked = sync_tangent(tuple(leaves[p] for p in b.paths),
                              axis_name, b.tag)
        for p, v in zip(b.paths, marked):
            leaves[p] = v
    return jax.tree_util.tree_unflatten(
        flat[1], [leaves[_path_str(path)] for path, _ in flat[0]])


def apply_bucket_sync(grads, buckets: Sequence[Bucket], axis_name):
    """Forward (non-autodiff) bucket sync of an already-accumulated
    gradient tree — the ``grad_accum > 1`` path: local gradients are
    accumulated across microbatches and all-reduced ONCE per optimizer
    step, one psum per bucket. Same flat-buffer arithmetic as the marker
    backward, so fused-vs-bucketed stays bit-exact. Leaves outside every
    bucket pass through unsynced (the in-scan set is never routed here:
    accumulation disables in-scan marking)."""
    flat = jax.tree_util.tree_flatten_with_path(grads)
    leaves = {_path_str(path): leaf for path, leaf in flat[0]}
    for b in buckets:
        synced = _flat_psum(tuple(leaves[p] for p in b.paths),
                            axis_name, b.tag)
        for p, v in zip(b.paths, synced):
            leaves[p] = v
    return jax.tree_util.tree_unflatten(
        flat[1], [leaves[_path_str(path)] for path, _ in flat[0]])


# ---------------------------------------------------------------------------
# the in-scan sync hook (trace-time context, model side)
# ---------------------------------------------------------------------------

_tls = threading.local()


def _scan_stack() -> list:
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


class scan_sync_scope:
    """Trace-time context the Trainer opens around the model forward when
    per-layer in-scan sync should engage: ``axis_name`` is the dp axis
    (or None for an explicit no-op scope). The model's scan body asks
    :func:`current_scan_sync` / :func:`sync_scan_slice`."""

    def __init__(self, axis_name: Optional[str]):
        self.axis_name = axis_name

    def __enter__(self):
        _scan_stack().append(self.axis_name)
        return self

    def __exit__(self, *exc):
        _scan_stack().pop()
        return False


def current_scan_sync() -> Optional[str]:
    stack = _scan_stack()
    return stack[-1] if stack else None


def sync_scan_slice(tree, tag: str = "scan_layer"):
    """Model-side hook: wrap a scan body's PER-LAYER parameter slice in a
    sync marker when an in-scan scope is active (one all-reduce per layer
    iteration of the scan transpose — the remat'd stack's gradients
    participate in the overlap instead of waiting for the whole scan
    backward). Identity when no scope is active (init, eval, implicit
    sync, accumulation).

    Leaves are grouped by dtype — one marker (one flat psum) per dtype
    group, mirroring :func:`partition_buckets`' rule: the flat buffer
    cannot mix dtypes (``concatenate`` would silently promote and the
    cotangents would come back wrong-typed). Non-inexact leaves (no
    cotangent) pass through unmarked."""
    axis = current_scan_sync()
    if axis is None:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    groups: Dict[str, List[int]] = {}
    for i, leaf in enumerate(leaves):
        dt = getattr(leaf, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.inexact):
            continue
        groups.setdefault(str(np.dtype(dt)), []).append(i)
    out = list(leaves)
    for dt, idxs in sorted(groups.items()):
        gtag = tag if len(groups) == 1 else f"{tag}_{dt}"
        synced = sync_tangent(tuple(out[i] for i in idxs), axis, gtag)
        for i, v in zip(idxs, synced):
            out[i] = v
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# mode resolution + graceful fallback
# ---------------------------------------------------------------------------

def resolve_grad_sync(mode: Optional[str], mesh, dp_axis: str,
                      param_specs=None) -> Tuple[Optional[str], Optional[str]]:
    """Decide whether an explicit sync mode can engage on this mesh.

    Returns ``(active_mode, reason)``: ``active_mode`` is the requested
    mode, or None with a human-readable ``reason`` when the request must
    degrade to the implicit GSPMD sync — no dp axis, a 1-device dp axis
    (nothing to sync), or parameters sharded over the dp axis itself
    (FSDP-style layouts: their "grads" are shards, not replicas; the
    implicit partitioner sync is already correct and minimal there).
    Degrading is deliberate: ``grad_sync=`` must never crash a config
    that trains fine without it."""
    if mode is None:
        return None, None
    if mode not in GRAD_SYNC_MODES:
        raise ValueError(
            f"grad_sync must be one of {GRAD_SYNC_MODES}, got {mode!r}")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if dp_axis not in sizes:
        return None, f"mesh has no {dp_axis!r} axis (axes: {list(sizes)})"
    if sizes[dp_axis] <= 1:
        return None, f"dp axis {dp_axis!r} has a single device"
    if param_specs is not None:
        for spec in jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P)):
            if isinstance(spec, P) and any(
                    dp_axis == ax or (isinstance(ax, (tuple, list))
                                      and dp_axis in ax)
                    for ax in spec if ax is not None):
                return None, (f"param_sharding shards parameters over the "
                              f"dp axis {dp_axis!r} (FSDP-style layout)")
    return mode, None
