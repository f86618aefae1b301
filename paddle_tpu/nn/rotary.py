"""Rotary position embedding: positions enter attention as a rotation of
query and key, so a model that uses it has no position table and no
longest sequence of its own.

Pairing convention: rotate-half. Column ``i`` of the first half is paired
with column ``i + d/2`` of the second, both turned by the angle
``position * base ** (-2 i / d)``. (The interleaved convention pairs
columns ``2i`` and ``2i + 1``; the two differ by a fixed permutation of
the columns of the projections that feed them.) Angles, sines and cosines
are float32 whatever the input is; the result has the input's dtype.

Two variants beside the plain one. PARTIAL rotation turns the first
``dim`` values of a head and passes the others through
(:func:`apply_rotary` with ``cos`` narrower than half the head). YaRN
(:func:`yarn_frequencies`) stretches the slow frequencies by ``factor``
and leaves the fast ones alone, with a linear ramp between the two
"correction dimensions", and scales cosines and sines by an
``attention_factor``.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np
import jax.numpy as jnp

__all__ = ["rotary_angles", "apply_rotary", "yarn_frequencies"]


def yarn_frequencies(dim: int, base: float, factor: float,
                     original_len: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's ``dim // 2`` inverse frequencies, float32 (a constant of the
    configuration: numpy). Frequency ``i`` is a blend of the unscaled
    ``base ** (-2 i / dim)`` and that over ``factor``: unscaled below the
    correction dimension of ``beta_fast`` rotations over ``original_len``
    positions, divided above that of ``beta_slow``, a linear ramp
    between."""
    def correction_dim(rotations):
        return dim * math.log(original_len / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def rotary_angles(positions, dim: int, base: float,
                  inv_freq: Optional[np.ndarray] = None,
                  factor: float = 1.0):
    """``(cos, sin)`` of shape ``positions.shape + (dim // 2,)``,
    float32. ``inv_freq`` (``[dim // 2]``, e.g. :func:`yarn_frequencies`)
    takes the place of ``base ** (-2 i / dim)``; ``factor`` multiplies
    both results (YaRN's attention factor)."""
    if inv_freq is None:
        inv_freq = jnp.asarray(base, jnp.float32) ** (
            -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32)
    if factor == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def apply_rotary(x, cos, sin):
    """``x [..., d]`` rotated by the angles of its position: ``cos`` and
    ``sin`` ``[..., d/2]`` broadcast against ``x``'s leading axes (a head
    axis between position and ``d`` takes a ``[:, None]`` from the
    caller). Where ``cos`` is narrower than ``d / 2`` the rotation is
    PARTIAL: the first ``2 * cos.shape[-1]`` values turn (rotate-half
    among themselves) and the others pass through."""
    half = cos.shape[-1]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x32[..., 2 * half:]], axis=-1).astype(x.dtype)
