"""Rotary positions for packed documents, with YaRN's frequencies.

- ``document_positions``: a token's position INSIDE its own document, from
  ``segment_ids`` (every document a contiguous run of one id): positions
  restart at every document of a packed sequence.
- ``yarn_inv_freq``: the per-pair frequencies of YaRN (Peng et al. 2023,
  arXiv:2309.00071) as DeepSeek-V2's published modelling code computes them
  from ``rope_scaling``: pair ``i`` of ``dim / 2`` blends the plain
  ``theta^(-2i/dim)`` and the same divided by ``factor`` along a linear ramp
  between the two correction dimensions (the pairs that turn ``beta_fast``
  and ``beta_slow`` times within the original context).
- ``yarn_mscale``: ``0.1 * mscale * ln(factor) + 1`` (1 for a factor <= 1).
- ``apply_rotary``: pairs ``(2i, 2i + 1)`` of the last axis turn together;
  the result is laid out as that code lays it out (all first elements, then
  all second elements).  Only the product of a rotated query with a rotated
  key is ever used, and that does not depend on the layout.

- ``plain_inv_freq``, ``sectioned_angles``, ``apply_rotary_halves``: rotary
  positions in SECTIONS (Qwen2-VL's multimodal rotary embedding, which
  Keye-VL-2.0's language model takes over: ``rope_scaling.mrope_section``).  A
  token has three position ids (temporal, height, width); the ``dim / 2``
  frequency pairs are cut into three runs of ``sections`` pairs and run ``a``
  turns by id ``a``.  Text tokens have three equal ids, and the result is the
  plain rotation.  Pair ``i`` is elements ``(i, i + dim / 2)`` (the published
  ``rotate_half``), and the layout is kept.

Angles, cosines and sines are float32; the rotated vector goes back to the
input's dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def document_positions(segment_ids):
    """(batch, T) int32 -> (batch, T) int32: 0 at a document's first token."""
    t = segment_ids.shape[-1]
    idx = jnp.arange(t, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones_like(segment_ids[..., :1], bool),
                             segment_ids[..., 1:] != segment_ids[..., :-1]], axis=-1)
    start = jax.lax.cummax(jnp.where(first, idx, 0), axis=segment_ids.ndim - 1)
    return idx - start


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(turns: float, dim: int, theta: float, original: int) -> float:
    return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int, beta_fast: float, beta_slow: float):
    """(dim / 2,) float32 frequencies."""
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    low = max(math.floor(_correction_dim(beta_fast, dim, theta, original)), 0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, theta, original)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def apply_rotary(x, positions, inv_freq, scale: float = 1.0):
    """``x`` (batch, T, ..., dim) with ``positions`` (batch, T)."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (batch, T, dim / 2)
    angles = angles.reshape(*angles.shape[:2], *([1] * (x.ndim - 3)), angles.shape[-1])
    cos, sin = scale * jnp.cos(angles), scale * jnp.sin(angles)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def plain_inv_freq(dim: int, theta: float):
    """(dim / 2,) float32: ``theta^(-2i / dim)``."""
    return 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def sectioned_angles(position_ids, inv_freq, sections: tuple[int, ...]):
    """``position_ids`` (sections, batch, T) int32, ``inv_freq`` (pairs,) with
    ``sum(sections) == pairs`` -> (batch, T, pairs) float32 angles: the pairs
    of run ``a`` turn by ``position_ids[a]``."""
    assert sum(sections) == inv_freq.shape[0] and len(sections) == position_ids.shape[0]
    edges = [sum(sections[:a]) for a in range(len(sections) + 1)]
    return jnp.concatenate([position_ids[a].astype(jnp.float32)[..., None] * inv_freq[lo:hi]
                            for a, (lo, hi) in enumerate(zip(edges, edges[1:]))], axis=-1)


def apply_rotary_halves(x, angles):
    """``x`` (batch, T, ..., dim) with ``angles`` (batch, T, dim / 2): elements
    ``(i, i + dim / 2)`` turn together by ``angles[..., i]``."""
    angles = angles.reshape(*angles.shape[:2], *([1] * (x.ndim - 3)), angles.shape[-1])
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)
