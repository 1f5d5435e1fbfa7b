"""Rotary positions with YaRN scaling, for a plain rotated dimension.

Written once for the families whose rotation is more than ``llama.rope``'s one
theta (docs/SERVING.md §Two kinds of page, "a rotation a layer kind"):
``models/axk1.py`` rotates the ``rope_dim`` of its decoupled key part under
YaRN, ``models/mellum.py`` rotates whole heads under one table a KIND of layer
(plain on its window layers, YaRN on its full ones), ``models/longcat.py`` and
``models/bailing.py`` rotate plainly through :func:`rotate`.  Nothing here
reads a family's config: a table is its dimension, theta and YaRN's five
numbers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's factor on cos and sin (HF's ``attention_factor`` where the
    configuration does not state one): ``0.1 mscale ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float = 1.0, original_len: int = 0,
                  beta_fast: float = 32.0, beta_slow: float = 1.0) -> jax.Array:
    """The ``dim / 2`` inverse frequencies: per frequency a blend of the
    original ``theta^(-2i/dim)`` and the interpolated one (``/ factor``) by a
    linear ramp between the correction dimensions of ``beta_fast`` and
    ``beta_slow`` rotations over the original context — fast dimensions keep
    their frequency, slow ones are interpolated.  The correction dimensions
    are truncated (floor and ceil) and clipped to the dimension's range, as
    HF's ``_compute_yarn_parameters`` does by default.  ``factor`` <= 1 is
    plain RoPE."""
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if factor <= 1:
        return extra

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original_len / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def rotate(x: jax.Array, ang: jax.Array, ratio: float = 1.0) -> jax.Array:
    """x: [T, ..., dim] rotated by the angles ``ang`` [T, dim / 2]
    (position x inverse frequency), half-split pairing (dimension i with i +
    dim/2: HF's ``rotate_half``), cos and sin scaled by ``ratio``."""
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * ratio, jnp.sin(ang) * ratio
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


__all__ = ["rotate", "yarn_inv_freq", "yarn_mscale"]
