"""Seeded weights and the benchmark tokenizer, written as an HF checkpoint.

The program loads models from a checkpoint directory only (no entry takes a
parameter tree; PERF.md lists it), so the benchmark makes the tensors ON THE
DEVICE from the seed, one jitted program per layer kind, in the type they
are served in, and writes them in HF layout for the real loader to read.
The reference reads the same files.

Tensor tables are per `model_type` and live beside the plain reference:
`benchmarks/reference/<model_type>.py` exports `tensor_table(cfg)`, found
by name.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

# kind: "w" weight ~ N(0, 0.02), "router" ~ N(0, 0.05), "norm" ~ 1 + N(0, 0.1)
Table = Dict[str, Tuple[Tuple[int, ...], str]]


def reference_module(model_type: str):
    return importlib.import_module(f"benchmarks.reference.{model_type}")


def seed_key(seed: int):
    import jax

    seed = int(seed)
    # a seed may exceed 31 bits; fold the high part in
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _generate(table: Table, key, dtype):
    """One jitted program making every tensor of `table`."""
    import jax
    import jax.numpy as jnp

    names = sorted(table)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = table[name]
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if kind == "norm":
                x = 1.0 + 0.1 * x
            else:
                x = x * (0.05 if kind == "router" else 0.02)
            out[name] = x.astype(dtype)
        return out

    return jax.jit(make)(key)


def _to_host(tree: dict) -> Dict[str, np.ndarray]:
    """Device tensors -> numpy; a stacked "experts.*" tensor becomes one
    entry per expert (views, no copy)."""
    import jax

    host = jax.device_get(tree)
    out: Dict[str, np.ndarray] = {}
    for name, arr in host.items():
        if ".experts.*." in name:
            for e in range(arr.shape[0]):
                out[name.replace(".experts.*.", f".experts.{e}.")] = arr[e]
        else:
            out[name] = arr
    return out


def write_checkpoint(model_dir: Path, cfg: dict, seed: int, dtype: str = "bfloat16") -> int:
    """Seeded checkpoint in HF layout, one safetensors file per layer.
    Returns the bytes written.  `cfg` is the HF config as served."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file

    model_dir.mkdir(parents=True, exist_ok=True)
    ref = reference_module(cfg["model_type"])
    edge, layer_table = ref.tensor_table(cfg)
    key = seed_key(seed)
    dt = jnp.dtype(dtype)
    nbytes = 0

    def save(tree: dict, fname: str, prefix: str = "") -> None:
        nonlocal nbytes
        host = {prefix + k: v for k, v in _to_host(tree).items()}
        nbytes += sum(v.nbytes for v in host.values())
        save_file(host, str(model_dir / fname))

    save(_generate(edge, jax.random.fold_in(key, 1_000_000), dt), "model-edge.safetensors")
    # generate layer i+1 on the device while layer i is written
    pending = None
    for i in range(cfg["num_hidden_layers"]):
        tree = _generate(layer_table(i), jax.random.fold_in(key, i), dt)
        if pending is not None:
            save(*pending)
        pending = (tree, f"model-layer-{i:03d}.safetensors", f"model.layers.{i}.")
    if pending is not None:
        save(*pending)
    (model_dir / "config.json").write_text(json.dumps(cfg, indent=1))
    write_tokenizer(model_dir, cfg["vocab_size"])
    return nbytes


def write_tokenizer(model_dir: Path, vocab: int) -> None:
    """A word-level tokenizer: word `t<i>` is token i, and every token
    decodes to a non-empty word, so the server streams one chunk per token.
    It declares no end-of-sequence token, so an answer ends only at
    `max_tokens`."""
    tok = {
        "version": "1.0",
        "truncation": None,
        "padding": None,
        "added_tokens": [],
        "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None,
        "decoder": None,
        "model": {
            "type": "WordLevel",
            "vocab": {f"t{i}": i for i in range(vocab)},
            "unk_token": "t0",
        },
    }
    (model_dir / "tokenizer.json").write_text(json.dumps(tok))
    (model_dir / "tokenizer_config.json").write_text(
        json.dumps({"tokenizer_class": "PreTrainedTokenizerFast"})
    )


def token_id(word: str) -> int:
    return int(word.strip()[1:])
