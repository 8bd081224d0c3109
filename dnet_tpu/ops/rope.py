"""Rotary position embeddings (HF-compatible, incl. Llama-3 scaling).

Frequencies are computed once per model config and closed over by the jitted
step, so inside jit this is two multiplies and an add on the VPU — no tables
in HBM.  Covers the rope variants the reference inherits from mlx-lm's llama/
qwen3 models (reference: src/dnet/core/models/llama.py:106-117 drops HF
`rotary_emb.inv_freq` and recomputes, as we do).
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np


def rope_frequencies(
    head_dim: int,
    theta: float = 10000.0,
    scaling: Optional[dict[str, Any]] = None,
    max_position_embeddings: int = 8192,
) -> tuple[np.ndarray, float]:
    """(inv_freq [head_dim//2], attention_scaling) with HF `rope_scaling`.

    attention_scaling multiplies cos/sin (YaRN mscale); 1.0 for other types.
    Matches transformers.modeling_rope_utils for default/linear/llama3/yarn.
    """
    inv_freq = 1.0 / (
        theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    )
    attention_scaling = 1.0
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", ""))
        if rope_type == "yarn":
            factor = scaling.get("factor", 1.0)
            attention_factor = scaling.get("attention_factor")
            mscale = scaling.get("mscale")
            mscale_all_dim = scaling.get("mscale_all_dim")
            old_len = (
                scaling.get("original_max_position_embeddings")
                or max_position_embeddings
            )

            def get_mscale(scale, ms=1.0):
                if scale <= 1:
                    return 1.0
                return 0.1 * ms * math.log(scale) + 1.0

            if attention_factor is None:
                if mscale and mscale_all_dim:
                    attention_factor = float(
                        get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
                    )
                else:
                    attention_factor = get_mscale(factor)
            attention_scaling = float(attention_factor)

            beta_fast = scaling.get("beta_fast") or 32
            beta_slow = scaling.get("beta_slow") or 1
            dim = head_dim

            def correction_dim(num_rot):
                return (
                    dim * math.log(old_len / (num_rot * 2 * math.pi))
                ) / (2 * math.log(theta))

            low = correction_dim(beta_fast)
            high = correction_dim(beta_slow)
            if scaling.get("truncate", True):
                low, high = math.floor(low), math.ceil(high)
            low, high = max(low, 0), min(high, dim - 1)
            if low == high:
                high += 0.001
            ramp = np.clip(
                (np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1
            )
            extrapolation_factor = 1 - ramp
            inv_freq = (inv_freq / factor) * (1 - extrapolation_factor) + (
                inv_freq * extrapolation_factor
            )
        elif rope_type == "llama3":
            factor = scaling.get("factor", 8.0)
            low_factor = scaling.get("low_freq_factor", 1.0)
            high_factor = scaling.get("high_freq_factor", 4.0)
            old_len = scaling.get("original_max_position_embeddings", 8192)
            low_wavelen = old_len / low_factor
            high_wavelen = old_len / high_factor
            wavelen = 2 * math.pi / inv_freq
            scaled = np.where(wavelen > low_wavelen, inv_freq / factor, inv_freq)
            smooth = (old_len / wavelen - low_factor) / (high_factor - low_factor)
            mid = (1 - smooth) * inv_freq / factor + smooth * inv_freq
            is_mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
            inv_freq = np.where(is_mid, mid, scaled)
        elif rope_type in ("linear",):
            inv_freq = inv_freq / scaling.get("factor", 1.0)
        # "default"/None: unscaled
    return inv_freq.astype(np.float32), attention_scaling


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
    attention_scaling: float = 1.0,
) -> jnp.ndarray:
    """Rotate q or k.

    x: [B, T, N, head_dim] (head_dim even, half-split convention as in HF).
    positions: [B, T] or [T] absolute token positions.
    attention_scaling: YaRN mscale multiplier on cos/sin.

    A rotary width narrower than the head (HF `partial_rotary_factor`):
    `inv_freq` has R/2 entries for R < head_dim, the FIRST R dims rotate
    (half-split within them) and the rest pass through.
    """
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., T, R/2]
    if angles.ndim == 2:  # [T, R/2] -> broadcast over batch
        angles = angles[None]
    cos = (jnp.cos(angles) * attention_scaling)[:, :, None, :]  # [B, T, 1, R/2]
    sin = (jnp.sin(angles) * attention_scaling)[:, :, None, :]
    half = inv_freq.shape[-1]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if 2 * half < x.shape[-1]:
        return jnp.concatenate([out.astype(x.dtype), x[..., 2 * half:]], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
    attention_scaling: float = 1.0,
) -> jnp.ndarray:
    """Complex-pair (interleaved) rotary convention: pairs are (x[2i], x[2i+1]).

    DeepSeek-V2's apply_rotary_emb uses view_as_complex, i.e. this layout —
    NOT the half-split convention.  x: [B, T, N, D].
    """
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [.., T, D/2]
    if angles.ndim == 2:
        angles = angles[None]
    cos = (jnp.cos(angles) * attention_scaling)[:, :, None, :]
    sin = (jnp.sin(angles) * attention_scaling)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x_even = xf[..., 0::2]
    x_odd = xf[..., 1::2]
    out_even = x_even * cos - x_odd * sin
    out_odd = x_odd * cos + x_even * sin
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
