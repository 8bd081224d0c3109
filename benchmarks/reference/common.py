"""What the plain references share: tensor access and the standard pieces."""

from __future__ import annotations

from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from safetensors import safe_open


class Tensors:
    """Reads the seeded checkpoint's tensors by HF name, lazily."""

    def __init__(self, model_dir: Path):
        self._where: Dict[str, Path] = {}
        for f in sorted(Path(model_dir).glob("*.safetensors")):
            with safe_open(f, framework="numpy") as st:
                for name in st.keys():
                    self._where[name] = f

    def get(self, name: str) -> np.ndarray:
        with safe_open(self._where[name], framework="numpy") as st:
            return st.get_tensor(name)

    def layer(self, i: int) -> Dict[str, np.ndarray]:
        """Layer i's tensors by suffix; `mlp.experts.<e>.X` stacked as
        `mlp.experts.*.X` [E, ...]."""
        prefix = f"model.layers.{i}."
        f = self._where[prefix + "input_layernorm.weight"]
        flat, experts = {}, {}
        with safe_open(f, framework="numpy") as st:
            for name in st.keys():
                if not name.startswith(prefix):
                    continue
                suffix = name[len(prefix):]
                if suffix.startswith("mlp.experts."):
                    _, _, e, rest = suffix.split(".", 3)
                    experts.setdefault(rest, {})[int(e)] = st.get_tensor(name)
                else:
                    flat[suffix] = st.get_tensor(name)
        for rest, by_e in experts.items():
            flat[f"mlp.experts.*.{rest}"] = np.stack([by_e[e] for e in range(len(by_e))])
        return flat


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(w)


def rotate_half(x):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-b, a], axis=-1)


def causal_attention(q, k, v, scale):
    """q [T,H,Dq], k [T,H,Dq], v [T,H,Dv] -> [T,H,Dv]; full causal softmax."""
    T = q.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


def swiglu(x, w_gate, w_up, w_down):
    """HF [out, in] weights."""
    return (jax.nn.silu(x @ f32(w_gate).T) * (x @ f32(w_up).T)) @ f32(w_down).T


def routed_experts(x, top_idx, top_w, e_gate, e_up, e_down):
    """Every expert on every token, weighted by the routing (zero where an
    expert was not chosen): the definition, not an implementation.
    x [T,D]; e_* [E, out, in]; top_idx/top_w [T,k]."""
    E = e_gate.shape[0]
    weight = jnp.zeros((x.shape[0], E), jnp.float32)
    weight = weight.at[jnp.arange(x.shape[0])[:, None], top_idx].add(top_w)
    h = jax.nn.silu(jnp.einsum("td,efd->tef", x, f32(e_gate))) * jnp.einsum(
        "td,efd->tef", x, f32(e_up)
    )
    y = jnp.einsum("tef,edf->ted", h, f32(e_down))
    return jnp.einsum("ted,te->td", y, weight)
