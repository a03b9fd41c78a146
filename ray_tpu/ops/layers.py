"""Elementwise / normalization / positional building blocks.

Pure-JAX ops that XLA fuses into surrounding matmuls (per the HBM-bandwidth
guidance: no hand-scheduling of what the compiler already fuses). Kept
dtype-disciplined: params may be f32 while activations run bf16; norms
accumulate in f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


def rope_cache(seq_len: int, head_dim: int, base: float = 10000.0):
    """(cos, sin) tables, f32, [seq, head_dim//2]."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def rope(x: jax.Array, cos: jax.Array, sin: jax.Array, positions=None) -> jax.Array:
    """Rotary embedding. x: [batch, seq, heads, head_dim]."""
    if positions is not None:
        cos = cos[positions]
        sin = sin[positions]
    # cos/sin: [seq, hd/2] -> broadcast over batch and heads
    while cos.ndim < x.ndim - 1:
        cos = cos[None]
        sin = sin[None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    # [b, s, h, hd/2] * [1?, s, 1, hd/2]
    c = jnp.expand_dims(cos, -2)
    s = jnp.expand_dims(sin, -2)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


def rope_partial(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``rope`` on the first ``2 * cos.shape[-1]`` of the head's dimensions
    (rotate-half within them); the rest pass unchanged. With tables as wide
    as half the head it is ``rope``."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return rope(x, cos, sin)
    return jnp.concatenate(
        [rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


def yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0,
                  truncate: bool = True):
    """YaRN's inverse frequencies [dim // 2] float32 (numpy: constants of
    the traced program): a blend of ``base ** (-2i / dim)`` (kept where a
    dimension turns more than ``beta_fast`` times over ``original_max``
    positions) and the same divided by ``factor`` (where it turns fewer
    than ``beta_slow`` times), linear between. The formula is
    transformers' ``modeling_rope_utils._compute_yarn_parameters`` (4.57),
    which tests/test_laguna.py holds it to."""
    import math

    import numpy as np

    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001  # as the published code: no division by zero
    pos_freqs = np.float32(base) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    extrapolation = np.float32(1.0) / pos_freqs
    interpolation = np.float32(1.0) / (np.float32(factor) * pos_freqs)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float32) - np.float32(low))
        / np.float32(high - low), 0, 1).astype(np.float32)
    keep = 1 - ramp
    return (interpolation * (1 - keep) + extrapolation * keep).astype(
        np.float32)
