"""Rotary position embedding: positions enter attention as a rotation of
query and key, so a model that uses it has no position table and no
longest sequence of its own.

Pairing convention: rotate-half. Column ``i`` of the first half is paired
with column ``i + d/2`` of the second, both turned by the angle
``position * base ** (-2 i / d)``. (The interleaved convention pairs
columns ``2i`` and ``2i + 1``; the two differ by a fixed permutation of
the columns of the projections that feed them.) Angles, sines and cosines
are float32 whatever the input is; the result has the input's dtype.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["rotary_angles", "apply_rotary"]


def rotary_angles(positions, dim: int, base: float):
    """``(cos, sin)`` of shape ``positions.shape + (dim // 2,)``,
    float32."""
    inv = jnp.asarray(base, jnp.float32) ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """``x [..., d]`` rotated by the angles of its position: ``cos`` and
    ``sin`` ``[..., d/2]`` broadcast against ``x``'s leading axes (a head
    axis between position and ``d`` takes a ``[:, None]`` from the
    caller)."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)
