"""The one decision between compiling a Pallas kernel and interpreting it.

Every kernel module (``pallas_attention``, ``pallas_conv``, ``fused_ln``)
and the serving engine's attention choice ask :func:`interpret` and
nothing else, so the rule cannot drift between them. On a TPU the kernels
compile through Mosaic and a kernel Mosaic refuses raises; there is no
fallback to the interpreter or to an XLA path. Anywhere else (the CPU
test harness) the same kernels run through the Pallas interpreter.
"""

from __future__ import annotations

import jax

__all__ = ["interpret"]


def interpret() -> bool:
    """True when Pallas kernels must run interpreted (no TPU backend)."""
    return jax.default_backend() != "tpu"
