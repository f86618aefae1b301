"""Opt-in XLA flag assembly for TPU collective/compute overlap (ISSUE 8).

``Trainer(grad_sync="bucketed")`` gives the scheduler per-bucket
all-reduces it CAN float under the backward — whether it actually does
is the backend scheduler's call. On TPU, XLA's async-collective fusion
and latency-hiding scheduler are what turn an eligible schedule into an
overlapped one; several of the relevant passes sit behind flags. This
module assembles that flag set with documented provenance so a run
script does::

    from paddle_tpu.obs import xla_flags
    xla_flags.apply_overlap_flags()        # BEFORE importing/initializing jax
    # or: XLA_FLAGS="$(python -m paddle_tpu.obs.xla_flags)" python train.py

CAVEATS (read before enabling):

- Flags are parsed ONCE at backend initialization: `apply_overlap_flags`
  must run before jax creates its TPU client, or the flags are silently
  ignored (the helper warns when jax looks initialized).
- These are ``--xla_tpu_*`` / scheduler tunables, NOT stable API: names
  drift across libtpu releases, and an unknown flag aborts the runtime.
  `strict=False` (default) keeps only the conservative core set; pass
  `strict=True` to get everything and accept the version risk.
- On CPU/GPU backends the TPU flags are inert at best; the helper is a
  no-op unless ``force=True``.

Provenance of the set (public sources, same pattern as the
``PEAK_FLOPS`` table):

- ``xla_tpu_enable_async_collective_fusion*`` and
  ``xla_tpu_overlap_compute_collective_tc`` — the async-collective +
  compute/collective overlap set published in Google's MaxText/
  accelerator-microbenchmark repos as the TPU performance baseline.
- ``xla_tpu_enable_data_parallel_all_reduce_opt`` and
  ``xla_tpu_data_parallel_opt_different_sized_ops`` — dp all-reduce
  scheduling optimizations from the same set (precisely the gradient
  all-reduce this PR buckets).
- ``xla_enable_async_all_gather`` / ``xla_enable_async_collective_permute``
  — async lowering of the remaining collective kinds (XLA flag registry,
  ``xla/debug_options_flags.cc``).
"""

from __future__ import annotations

import logging
import os
import sys
from typing import Dict, List, Optional

_log = logging.getLogger("paddle_tpu.obs.xla_flags")

__all__ = ["OVERLAP_FLAGS", "EXTENDED_FLAGS", "overlap_flags",
           "merge_xla_flags", "apply_overlap_flags"]

# The conservative core: async collective fusion + compute/collective
# overlap + dp all-reduce scheduling. Widely exercised together on
# v4/v5e/v5p-era libtpu.
OVERLAP_FLAGS: Dict[str, str] = {
    "--xla_tpu_enable_async_collective_fusion": "true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather": "true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps": "true",
    "--xla_tpu_overlap_compute_collective_tc": "true",
    "--xla_tpu_enable_data_parallel_all_reduce_opt": "true",
    "--xla_tpu_data_parallel_opt_different_sized_ops": "true",
}

# The version-riskier extras (strict=True): async lowering for the
# non-all-reduce collective kinds.
EXTENDED_FLAGS: Dict[str, str] = {
    "--xla_enable_async_all_gather": "true",
    "--xla_enable_async_collective_permute": "true",
}


def overlap_flags(strict: bool = False) -> List[str]:
    """The overlap flag set as ``--flag=value`` strings. ``strict=True``
    appends the extended set (see module docstring for the risk note)."""
    flags = dict(OVERLAP_FLAGS)
    if strict:
        flags.update(EXTENDED_FLAGS)
    return [f"{k}={v}" for k, v in flags.items()]


def merge_xla_flags(new_flags: List[str],
                    existing: Optional[str] = None) -> str:
    """Merge flags into an XLA_FLAGS string. An operator-set value for
    the same flag WINS (the helper must never silently override an
    explicit choice); order is existing-first."""
    existing = existing if existing is not None \
        else os.environ.get("XLA_FLAGS", "")
    have = {f.split("=", 1)[0] for f in existing.split() if f}
    merged = [f for f in existing.split() if f]
    merged += [f for f in new_flags if f.split("=", 1)[0] not in have]
    return " ".join(merged)


def _jax_initialized() -> bool:
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        # xla_bridge caches live clients post-init; inspect without
        # triggering initialization ourselves
        from jax._src import xla_bridge
        backends = getattr(xla_bridge, "_backends", None)
        if backends is None:
            # probe point moved in a newer jax: assume the worst (a
            # silent False here would defeat the warning this helper
            # exists to give)
            return True
        return bool(backends)
    except Exception:
        return True      # jax imported and unprobeable: assume the worst


def apply_overlap_flags(strict: bool = False, force: bool = False,
                        env: Optional[Dict[str, str]] = None) -> str:
    """Merge the overlap set into ``env['XLA_FLAGS']`` (default
    ``os.environ``) and return the resulting string. No-op (with a log
    line) unless a TPU looks reachable (``JAX_PLATFORMS``/``TPU_*`` env
    hints) or ``force=True``; warns when jax already initialized a
    backend — at that point the flags cannot take effect in this
    process."""
    env = os.environ if env is None else env
    hints = env.get("JAX_PLATFORMS", "")
    tpu_likely = ("tpu" in hints.lower()
                  or any(k.startswith(("TPU_", "LIBTPU")) for k in env))
    if not (tpu_likely or force):
        _log.info("apply_overlap_flags: no TPU hints in the environment "
                  "and force=False — leaving XLA_FLAGS untouched")
        return env.get("XLA_FLAGS", "")
    if _jax_initialized():
        _log.warning(
            "apply_overlap_flags: jax has already initialized a backend — "
            "XLA_FLAGS changes will NOT take effect in this process; set "
            "them before importing jax (or via the shell)")
    merged = merge_xla_flags(overlap_flags(strict=strict),
                             existing=env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = merged
    return merged


if __name__ == "__main__":    # XLA_FLAGS="$(python -m paddle_tpu.obs.xla_flags)"
    strict = "--strict" in sys.argv[1:]
    print(merge_xla_flags(overlap_flags(strict=strict)))
