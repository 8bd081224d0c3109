"""Normalization layers (functional, f32 accumulation on the VPU)."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm with float32 accumulation, cast back to x.dtype.

    Matches HF LlamaRMSNorm: y = w * x / sqrt(mean(x^2) + eps).
    """
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def rms_norm0(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """The ZERO-CENTRED RMSNorm (Qwen3-Next, Gemma): y = (1 + w) * x /
    sqrt(mean(x^2) + eps), a weight of 0 being the identity scale; float32
    accumulation, cast back to x.dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Mean-subtracting LayerNorm with a weight and NO bias (Cohere's
    CohereLayerNorm): y = w * (x - mean(x)) / sqrt(var(x) + eps), float32
    accumulation, cast back to x.dtype."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)
