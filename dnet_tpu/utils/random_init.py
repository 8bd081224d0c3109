"""Random parameter initialization (bench/dryrun/test fixtures).

Builds the same stacked-window + edge param pytrees the checkpoint loader
produces, but from a config alone — no weights on disk.  Zero-egress
benchmarking runs on synthetic weights with real model shapes.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from dnet_tpu.models.base import ModelConfig

LLAMA_3_2_1B_CONFIG = {
    "model_type": "llama",
    "vocab_size": 128256,
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_hidden_layers": 16,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "head_dim": 64,
    "rms_norm_eps": 1e-5,
    "rope_theta": 500000.0,
    "rope_scaling": {
        "rope_type": "llama3",
        "factor": 32.0,
        "low_freq_factor": 1.0,
        "high_freq_factor": 4.0,
        "original_max_position_embeddings": 8192,
    },
    "max_position_embeddings": 131072,
    "tie_word_embeddings": True,
}

LLAMA_3_8B_CONFIG = {
    "model_type": "llama",
    "vocab_size": 128256,
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "head_dim": 128,
    "rms_norm_eps": 1e-5,
    "rope_theta": 500000.0,
    "max_position_embeddings": 8192,
    "tie_word_embeddings": False,
}


def random_llama_params(
    cfg: ModelConfig,
    layers: Sequence[int],
    dtype: str = "bfloat16",
    seed: int = 0,
) -> Tuple[Dict, Dict]:
    """(stacked window params, edge params) with real shapes, random values."""
    L = len(list(layers))
    D, F = cfg.hidden_size, cfg.intermediate_size
    H, KVH, Hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    V = cfg.vocab_size
    dt = jnp.dtype(dtype)
    key = jax.random.key(seed)
    ks = iter(jax.random.split(key, 16))

    def w(*shape, scale=0.02):
        return (jax.random.normal(next(ks), shape, dtype=jnp.float32) * scale).astype(dt)

    window = {
        "attn_norm": jnp.ones((L, D), dtype=dt),
        "wq": w(L, D, H * Hd),
        "wk": w(L, D, KVH * Hd),
        "wv": w(L, D, KVH * Hd),
        "wo": w(L, H * Hd, D),
        "mlp_norm": jnp.ones((L, D), dtype=dt),
        "w_gate": w(L, D, F),
        "w_up": w(L, D, F),
        "w_down": w(L, F, D),
    }
    edge = {
        "embed": {"weight": w(V, D)},
        "final_norm": {"weight": jnp.ones((D,), dtype=dt)},
    }
    if not cfg.tie_word_embeddings:
        edge["lm_head"] = {"weight": w(D, V)}
    return window, edge


# ---- seeded HF-format checkpoints on disk ---------------------------------
# What `--model <dir>` loads through the real loader (Checkpoint -> stacked
# params -> device) when there is no network to fetch published weights:
# the published widths with seeded random values.  No tokenizer files are
# written, so load_tokenizer serves ByteTokenizer.

#: tier-1-size stand-in for rehearsals off the chip (tests/fakes' tiny llama)
TINY_LLAMA_CONFIG = {
    "model_type": "llama",
    "vocab_size": 261,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0,
    "max_position_embeddings": 512,
    "tie_word_embeddings": False,
}

CHECKPOINT_CONFIGS = {
    "llama-3.2-1b": LLAMA_3_2_1B_CONFIG,
    "tiny-llama": TINY_LLAMA_CONFIG,
}


def write_random_llama_checkpoint(
    out_dir, config: Dict, seed: int = 0, dtype: str = "bfloat16"
) -> bool:
    """Write a seeded llama checkpoint in HF layout, one safetensors file
    per layer (peak host memory is one layer, not the model).  Returns
    False without writing when `out_dir` already holds one with the same
    config, seed and dtype.  config.json is written last, so a directory
    that has it is complete."""
    import json
    from pathlib import Path

    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    out = Path(out_dir)
    cfg = {
        **config,
        "architectures": ["LlamaForCausalLM"],
        "torch_dtype": dtype,
        "hidden_act": "silu",
        "attention_bias": False,
        "mlp_bias": False,
        "dnet_random_init_seed": seed,
    }
    cfg_path = out / "config.json"
    if cfg_path.is_file() and json.loads(cfg_path.read_text()) == cfg:
        return False
    out.mkdir(parents=True, exist_ok=True)
    for stale in out.glob("*.safetensors"):
        stale.unlink()
    cfg_path.unlink(missing_ok=True)

    np_dtype = ml_dtypes.bfloat16 if dtype == "bfloat16" else np.dtype(dtype)
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim") or D // H
    V = cfg["vocab_size"]
    rng = np.random.default_rng(seed)

    def w(*shape):
        # HF's initializer_range; cast as we go so f32 never outlives a tensor
        x = rng.standard_normal(shape, dtype=np.float32)
        x *= 0.02
        return x.astype(np_dtype)

    def ones(n):
        return np.ones((n,), dtype=np_dtype)

    edge = {"model.embed_tokens.weight": w(V, D), "model.norm.weight": ones(D)}
    if not cfg["tie_word_embeddings"]:
        edge["lm_head.weight"] = w(V, D)
    save_file(edge, str(out / "model-edge.safetensors"))
    del edge
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        save_file(
            {
                p + "input_layernorm.weight": ones(D),
                p + "post_attention_layernorm.weight": ones(D),
                p + "self_attn.q_proj.weight": w(H * Hd, D),
                p + "self_attn.k_proj.weight": w(KVH * Hd, D),
                p + "self_attn.v_proj.weight": w(KVH * Hd, D),
                p + "self_attn.o_proj.weight": w(D, H * Hd),
                p + "mlp.gate_proj.weight": w(F, D),
                p + "mlp.up_proj.weight": w(F, D),
                p + "mlp.down_proj.weight": w(D, F),
            },
            str(out / f"model-layer-{i:03d}.safetensors"),
        )
    cfg_path.write_text(json.dumps(cfg, indent=2))
    return True


def main(argv=None) -> int:
    import argparse
    import json
    from pathlib import Path

    p = argparse.ArgumentParser(
        prog="python -m dnet_tpu.utils.random_init",
        description="write a seeded random-weight llama checkpoint (HF layout)",
    )
    p.add_argument("--out", required=True)
    p.add_argument("--config", choices=sorted(CHECKPOINT_CONFIGS), required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", default="bfloat16")
    args = p.parse_args(argv)
    written = write_random_llama_checkpoint(
        args.out, CHECKPOINT_CONFIGS[args.config], seed=args.seed, dtype=args.dtype
    )
    nbytes = sum(f.stat().st_size for f in Path(args.out).glob("*.safetensors"))
    print(json.dumps({
        "dir": str(args.out), "config": args.config, "seed": args.seed,
        "written": written, "safetensors_bytes": nbytes,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
