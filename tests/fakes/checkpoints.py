"""Tiny locally-generated HF-format checkpoints (no network, ever).

The analog of the reference's generated-safetensors test fixtures
(tests/test_layer_manager.py pattern): random-weight models small enough to
cross-check against `transformers` on CPU.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from dnet_tpu.utils.checkpoint import save_checkpoint

TINY_LLAMA_CONFIG = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "vocab_size": 261,  # byte tokenizer: 256 bytes + bos/eos + pad to odd size on purpose
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
    "attention_bias": False,
    "mlp_bias": False,
    "hidden_act": "silu",
    "torch_dtype": "float32",
    "bos_token_id": 256,
    "eos_token_id": 257,
}


TINY_QWEN3_CONFIG = {
    "architectures": ["Qwen3ForCausalLM"],
    "model_type": "qwen3",
    "vocab_size": 261,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0,
    "max_position_embeddings": 512,
    "tie_word_embeddings": False,
    "attention_bias": False,
    "hidden_act": "silu",
    "torch_dtype": "float32",
    "bos_token_id": 256,
    "eos_token_id": 257,
}


def make_tiny_qwen3(model_dir: str | Path, config: dict | None = None, seed: int = 1) -> dict:
    """Tiny Qwen3: Llama layout + per-head q/k norms."""
    cfg = dict(TINY_QWEN3_CONFIG)
    if config:
        cfg.update(config)
    rng = np.random.default_rng(seed)
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]

    def w(*shape, scale=0.05):
        return rng.normal(0.0, scale, size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones(D, dtype=np.float32),
        "lm_head.weight": w(V, D),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "self_attn.q_proj.weight"] = w(H * Hd, D)
        tensors[p + "self_attn.k_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.v_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.o_proj.weight"] = w(D, H * Hd)
        tensors[p + "self_attn.q_norm.weight"] = np.ones(Hd, np.float32) + w(Hd, scale=0.02)
        tensors[p + "self_attn.k_norm.weight"] = np.ones(Hd, np.float32) + w(Hd, scale=0.02)
        tensors[p + "mlp.gate_proj.weight"] = w(F, D)
        tensors[p + "mlp.up_proj.weight"] = w(F, D)
        tensors[p + "mlp.down_proj.weight"] = w(D, F)
    save_checkpoint(model_dir, cfg, tensors)
    return cfg


TINY_GPT_OSS_CONFIG = {
    "architectures": ["GptOssForCausalLM"],
    "model_type": "gpt_oss",
    "vocab_size": 261,
    "hidden_size": 64,
    "intermediate_size": 48,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_local_experts": 4,
    "num_experts_per_tok": 2,
    "sliding_window": 8,
    "layer_types": [
        "sliding_attention", "full_attention", "sliding_attention", "full_attention",
    ],
    "rms_norm_eps": 1e-5,
    "rope_theta": 150000.0,
    "rope_scaling": {
        "rope_type": "yarn",
        "factor": 32.0,
        "beta_fast": 32.0,
        "beta_slow": 1.0,
        "truncate": False,
        "original_max_position_embeddings": 4096,
    },
    "max_position_embeddings": 512,
    "tie_word_embeddings": False,
    "attention_bias": True,
    "attention_dropout": 0.0,
    "hidden_act": "silu",
    "torch_dtype": "float32",
    "bos_token_id": 256,
    "eos_token_id": 257,
}


def make_tiny_gpt_oss(model_dir: str | Path, config: dict | None = None, seed: int = 2) -> dict:
    """Tiny GPT-OSS: MoE + sinks + alternating SWA, HF dequantized layout."""
    cfg = dict(TINY_GPT_OSS_CONFIG)
    if config:
        cfg.update(config)
    rng = np.random.default_rng(seed)
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KVH, Hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    E = cfg["num_local_experts"]

    def w(*shape, scale=0.05):
        return rng.normal(0.0, scale, size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones(D, dtype=np.float32),
        "lm_head.weight": w(V, D),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "self_attn.q_proj.weight"] = w(H * Hd, D)
        tensors[p + "self_attn.q_proj.bias"] = w(H * Hd, scale=0.02)
        tensors[p + "self_attn.k_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.k_proj.bias"] = w(KVH * Hd, scale=0.02)
        tensors[p + "self_attn.v_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.v_proj.bias"] = w(KVH * Hd, scale=0.02)
        tensors[p + "self_attn.o_proj.weight"] = w(D, H * Hd)
        tensors[p + "self_attn.o_proj.bias"] = w(D, scale=0.02)
        tensors[p + "self_attn.sinks"] = w(H, scale=0.5)
        tensors[p + "mlp.router.weight"] = w(E, D)
        tensors[p + "mlp.router.bias"] = w(E, scale=0.02)
        tensors[p + "mlp.experts.gate_up_proj"] = w(E, D, 2 * F)
        tensors[p + "mlp.experts.gate_up_proj_bias"] = w(E, 2 * F, scale=0.02)
        tensors[p + "mlp.experts.down_proj"] = w(E, F, D)
        tensors[p + "mlp.experts.down_proj_bias"] = w(E, D, scale=0.02)
    save_checkpoint(model_dir, cfg, tensors)
    return cfg


TINY_DEEPSEEK_V2_CONFIG = {
    "architectures": ["DeepseekV2ForCausalLM"],
    "model_type": "deepseek_v2",
    "vocab_size": 261,
    "hidden_size": 64,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "head_dim": 8,  # == qk_rope_head_dim (drives rotary init in HF)
    "q_lora_rank": None,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "kv_lora_rank": 24,
    "v_head_dim": 12,
    "n_routed_experts": 4,
    "n_shared_experts": 1,
    "num_experts_per_tok": 2,
    "first_k_dense_replace": 1,
    "routed_scaling_factor": 1.0,
    "topk_method": "greedy",
    "norm_topk_prob": False,
    "n_group": 1,
    "topk_group": 1,
    "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0,
    "max_position_embeddings": 512,
    "tie_word_embeddings": False,
    "attention_bias": False,
    "attention_dropout": 0.0,
    "mlp_bias": False,
    "hidden_act": "silu",
    "aux_loss_alpha": 0.0,
    "seq_aux": True,
    "torch_dtype": "float32",
    "bos_token_id": 256,
    "eos_token_id": 257,
}


def make_tiny_deepseek_v2(model_dir: str | Path, config: dict | None = None, seed: int = 3) -> dict:
    """Tiny DeepSeek-V2: MLA + shared/routed MoE (layer 0 dense)."""
    cfg = dict(TINY_DEEPSEEK_V2_CONFIG)
    if config:
        cfg.update(config)
    rng = np.random.default_rng(seed)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H = cfg["num_attention_heads"]
    nope, rope_d = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    qk = nope + rope_d
    vd = cfg["v_head_dim"]
    kv_rank = cfg["kv_lora_rank"]
    E = cfg["n_routed_experts"]
    F, MF = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    SF = MF * cfg["n_shared_experts"]

    def w(*shape, scale=0.05):
        return rng.normal(0.0, scale, size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones(D, dtype=np.float32),
        "lm_head.weight": w(V, D),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        if cfg["q_lora_rank"] is None:
            tensors[p + "self_attn.q_proj.weight"] = w(H * qk, D)
        else:
            r = cfg["q_lora_rank"]
            tensors[p + "self_attn.q_a_proj.weight"] = w(r, D)
            tensors[p + "self_attn.q_a_layernorm.weight"] = np.ones(r, np.float32)
            tensors[p + "self_attn.q_b_proj.weight"] = w(H * qk, r)
        tensors[p + "self_attn.kv_a_proj_with_mqa.weight"] = w(kv_rank + rope_d, D)
        tensors[p + "self_attn.kv_a_layernorm.weight"] = np.ones(kv_rank, np.float32)
        tensors[p + "self_attn.kv_b_proj.weight"] = w(H * (nope + vd), kv_rank)
        tensors[p + "self_attn.o_proj.weight"] = w(D, H * vd)
        if i >= cfg["first_k_dense_replace"]:
            tensors[p + "mlp.gate.weight"] = w(E, D)
            for e in range(E):
                tensors[p + f"mlp.experts.{e}.gate_proj.weight"] = w(MF, D)
                tensors[p + f"mlp.experts.{e}.up_proj.weight"] = w(MF, D)
                tensors[p + f"mlp.experts.{e}.down_proj.weight"] = w(D, MF)
            tensors[p + "mlp.shared_experts.gate_proj.weight"] = w(SF, D)
            tensors[p + "mlp.shared_experts.up_proj.weight"] = w(SF, D)
            tensors[p + "mlp.shared_experts.down_proj.weight"] = w(D, SF)
        else:
            tensors[p + "mlp.gate_proj.weight"] = w(F, D)
            tensors[p + "mlp.up_proj.weight"] = w(F, D)
            tensors[p + "mlp.down_proj.weight"] = w(D, F)
    save_checkpoint(model_dir, cfg, tensors)
    return cfg


def make_tiny_llama(model_dir: str | Path, config: dict | None = None, seed: int = 0) -> dict:
    """Write a random-weight tiny Llama checkpoint; returns the config."""
    cfg = dict(TINY_LLAMA_CONFIG)
    if config:
        cfg.update(config)
    rng = np.random.default_rng(seed)
    D = cfg["hidden_size"]
    F = cfg["intermediate_size"]
    V = cfg["vocab_size"]
    H = cfg["num_attention_heads"]
    KVH = cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim", D // H)

    def w(*shape, scale=0.05):
        return rng.normal(0.0, scale, size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones(D, dtype=np.float32),
    }
    if not cfg["tie_word_embeddings"]:
        tensors["lm_head.weight"] = w(V, D)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "self_attn.q_proj.weight"] = w(H * Hd, D)
        tensors[p + "self_attn.k_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.v_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.o_proj.weight"] = w(D, H * Hd)
        tensors[p + "mlp.gate_proj.weight"] = w(F, D)
        tensors[p + "mlp.up_proj.weight"] = w(F, D)
        tensors[p + "mlp.down_proj.weight"] = w(D, F)
    save_checkpoint(model_dir, cfg, tensors)
    return cfg


TINY_MIXTRAL_CONFIG = {
    "architectures": ["MixtralForCausalLM"],
    "model_type": "mixtral",
    "vocab_size": 261,
    "hidden_size": 64,
    "intermediate_size": 96,  # per-expert FFN width
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_local_experts": 4,
    "num_experts_per_tok": 2,
    "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0,
    "max_position_embeddings": 512,
    "tie_word_embeddings": False,
    "attention_bias": False,
    "hidden_act": "silu",
    "torch_dtype": "float32",
    "bos_token_id": 256,
    "eos_token_id": 257,
    "sliding_window": None,
    "output_router_logits": False,
}


def make_tiny_mixtral(model_dir: str | Path, config: dict | None = None, seed: int = 5) -> dict:
    """Write a random-weight tiny Mixtral checkpoint (sparse top-k MoE)."""
    cfg = dict(TINY_MIXTRAL_CONFIG)
    if config:
        cfg.update(config)
    rng = np.random.default_rng(seed)
    D = cfg["hidden_size"]
    F = cfg["intermediate_size"]
    V = cfg["vocab_size"]
    H = cfg["num_attention_heads"]
    KVH = cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim", D // H)
    E = cfg["num_local_experts"]

    def w(*shape, scale=0.05):
        return rng.normal(0.0, scale, size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones(D, dtype=np.float32),
        "lm_head.weight": w(V, D),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "self_attn.q_proj.weight"] = w(H * Hd, D)
        tensors[p + "self_attn.k_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.v_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.o_proj.weight"] = w(D, H * Hd)
        tensors[p + "block_sparse_moe.gate.weight"] = w(E, D, scale=0.3)
        for e in range(E):
            q = p + f"block_sparse_moe.experts.{e}."
            tensors[q + "w1.weight"] = w(F, D)
            tensors[q + "w2.weight"] = w(D, F)
            tensors[q + "w3.weight"] = w(F, D)
    save_checkpoint(model_dir, cfg, tensors)
    return cfg


TINY_QWEN2_CONFIG = {
    "architectures": ["Qwen2ForCausalLM"],
    "model_type": "qwen2",
    "vocab_size": 261,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0,
    "max_position_embeddings": 512,
    "tie_word_embeddings": False,
    "hidden_act": "silu",
    "torch_dtype": "float32",
    "bos_token_id": 256,
    "eos_token_id": 257,
}


def make_tiny_qwen2(model_dir: str | Path, config: dict | None = None, seed: int = 6) -> dict:
    """Write a random-weight tiny Qwen2/2.5 checkpoint (biased q/k/v)."""
    cfg = dict(TINY_QWEN2_CONFIG)
    if config:
        cfg.update(config)
    rng = np.random.default_rng(seed)
    D = cfg["hidden_size"]
    F = cfg["intermediate_size"]
    V = cfg["vocab_size"]
    H = cfg["num_attention_heads"]
    KVH = cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim", D // H)

    def w(*shape, scale=0.05):
        return rng.normal(0.0, scale, size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones(D, dtype=np.float32),
        "lm_head.weight": w(V, D),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "self_attn.q_proj.weight"] = w(H * Hd, D)
        tensors[p + "self_attn.q_proj.bias"] = w(H * Hd, scale=0.1)
        tensors[p + "self_attn.k_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.k_proj.bias"] = w(KVH * Hd, scale=0.1)
        tensors[p + "self_attn.v_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.v_proj.bias"] = w(KVH * Hd, scale=0.1)
        tensors[p + "self_attn.o_proj.weight"] = w(D, H * Hd)
        tensors[p + "mlp.gate_proj.weight"] = w(F, D)
        tensors[p + "mlp.up_proj.weight"] = w(F, D)
        tensors[p + "mlp.down_proj.weight"] = w(D, F)
    save_checkpoint(model_dir, cfg, tensors)
    return cfg


TINY_QWEN3_MOE_CONFIG = {
    "architectures": ["Qwen3MoeForCausalLM"],
    "model_type": "qwen3_moe",
    "vocab_size": 261,
    "hidden_size": 64,
    "intermediate_size": 128,
    "moe_intermediate_size": 96,
    "num_hidden_layers": 4,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_experts": 4,
    "num_experts_per_tok": 2,
    "norm_topk_prob": True,
    "decoder_sparse_step": 1,
    "mlp_only_layers": [],
    "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0,
    "max_position_embeddings": 512,
    "tie_word_embeddings": False,
    "attention_bias": False,
    "hidden_act": "silu",
    "torch_dtype": "float32",
    "bos_token_id": 256,
    "eos_token_id": 257,
}


def make_tiny_qwen3_moe(model_dir: str | Path, config: dict | None = None, seed: int = 7) -> dict:
    """Write a random-weight tiny Qwen3-MoE checkpoint (q/k norms + MoE)."""
    cfg = dict(TINY_QWEN3_MOE_CONFIG)
    if config:
        cfg.update(config)
    rng = np.random.default_rng(seed)
    D = cfg["hidden_size"]
    F = cfg["moe_intermediate_size"]
    V = cfg["vocab_size"]
    H = cfg["num_attention_heads"]
    KVH = cfg["num_key_value_heads"]
    Hd = cfg.get("head_dim", D // H)
    E = cfg["num_experts"]

    def w(*shape, scale=0.05):
        return rng.normal(0.0, scale, size=shape).astype(np.float32)

    # mixed layouts (mlp_only_layers / decoder_sparse_step): dense layers
    # carry a plain swiglu MLP at intermediate_size, like transformers
    mlp_only = set(cfg.get("mlp_only_layers") or [])
    step = cfg.get("decoder_sparse_step", 1)

    def is_moe(i: int) -> bool:
        return i not in mlp_only and (step <= 1 or (i + 1) % step == 0)

    tensors = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones(D, dtype=np.float32),
        "lm_head.weight": w(V, D),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "post_attention_layernorm.weight"] = np.ones(D, np.float32) + w(D, scale=0.01)
        tensors[p + "self_attn.q_proj.weight"] = w(H * Hd, D)
        tensors[p + "self_attn.k_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.v_proj.weight"] = w(KVH * Hd, D)
        tensors[p + "self_attn.o_proj.weight"] = w(D, H * Hd)
        tensors[p + "self_attn.q_norm.weight"] = np.ones(Hd, np.float32) + w(Hd, scale=0.01)
        tensors[p + "self_attn.k_norm.weight"] = np.ones(Hd, np.float32) + w(Hd, scale=0.01)
        if is_moe(i):
            tensors[p + "mlp.gate.weight"] = w(E, D, scale=0.3)
            for e in range(E):
                q = p + f"mlp.experts.{e}."
                tensors[q + "gate_proj.weight"] = w(F, D)
                tensors[q + "up_proj.weight"] = w(F, D)
                tensors[q + "down_proj.weight"] = w(D, F)
        else:
            Fd = cfg["intermediate_size"]
            tensors[p + "mlp.gate_proj.weight"] = w(Fd, D)
            tensors[p + "mlp.up_proj.weight"] = w(Fd, D)
            tensors[p + "mlp.down_proj.weight"] = w(D, Fd)
    save_checkpoint(model_dir, cfg, tensors)
    return cfg


def tiny_brumby_config() -> dict:
    """The benchmark configuration `brumby-14b-8l` at its rehearsal size
    (hidden 64, heads 8/2 of 16, 2 layers): the HF keys alone."""
    import json

    root = Path(__file__).resolve().parents[2]
    full = json.loads((root / "benchmarks/configs/brumby-14b-8l.json").read_text())
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    cfg.update(full["rehearse"]["config"])
    return cfg


def make_tiny_brumby(model_dir: str | Path, seed: int = 2**31 + 32) -> dict:
    """A seeded float32 brumby checkpoint, written as the benchmark writes
    its own (tensor names from benchmarks/reference/brumby.py)."""
    from benchmarks.harness.weights import write_checkpoint

    cfg = tiny_brumby_config()
    write_checkpoint(Path(model_dir), cfg, seed=seed, dtype="float32")
    return cfg


def tiny_qwen3_next_config(**over) -> dict:
    """The benchmark configuration `qwen3-next-80b-a3b-4l-ep2` at its
    rehearsal size (hidden 64, 2 key / 4 value heads of 16, one period of
    4 layers, 16 experts held of 32, top-4): the HF keys alone."""
    import json

    root = Path(__file__).resolve().parents[2]
    full = json.loads(
        (root / "benchmarks/configs/qwen3-next-80b-a3b-4l-ep2.json").read_text()
    )
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    cfg.update(full["rehearse"]["config"])
    cfg.update(over)
    return cfg


def make_tiny_qwen3_next(model_dir: str | Path, seed: int = 2**31 + 37, **over) -> dict:
    """A seeded float32 qwen3_next checkpoint, written as the benchmark
    writes its own (tensor names from benchmarks/reference/qwen3_next.py)."""
    from benchmarks.harness.weights import write_checkpoint

    cfg = tiny_qwen3_next_config(**over)
    write_checkpoint(Path(model_dir), cfg, seed=seed, dtype="float32")
    return cfg


def tiny_mistral4_config(**over) -> dict:
    """The benchmark configuration `mistral-small-4-119b-6l-ep8` at its
    rehearsal size (hidden 64, 4 heads of 16 + 8 over a latent of 16, two
    layers, 4 experts held of 8, top-2, a(t)'s period 32): the HF keys
    alone."""
    import json

    root = Path(__file__).resolve().parents[2]
    full = json.loads(
        (root / "benchmarks/configs/mistral-small-4-119b-6l-ep8.json").read_text()
    )
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    cfg.update(full["rehearse"]["config"])
    cfg.update(over)
    return cfg


def make_tiny_mistral4(model_dir: str | Path, seed: int = 2**31 + 41, **over) -> dict:
    """A seeded float32 mistral4 checkpoint, written as the benchmark
    writes its own (tensor names from benchmarks/reference/mistral4.py)."""
    from benchmarks.harness.weights import write_checkpoint

    cfg = tiny_mistral4_config(**over)
    write_checkpoint(Path(model_dir), cfg, seed=seed, dtype="float32")
    return cfg


def tiny_minicpm_sala_config(**over) -> dict:
    """The benchmark configuration `minicpm-sala-8l` at its rehearsal size
    (hidden 64, 4 query / 2 KV heads of 16, 4 lightning heads of 16, two
    periods of [minicpm4, lightning-attn x 3]; blocks of 8 tokens chosen
    6 at a time past 64 tokens of context): the HF keys alone."""
    import json

    root = Path(__file__).resolve().parents[2]
    full = json.loads((root / "benchmarks/configs/minicpm-sala-8l.json").read_text())
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    cfg.update(full["rehearse"]["config"])
    cfg.update(over)
    return cfg


def make_tiny_minicpm_sala(model_dir: str | Path, seed: int = 2**31 + 43, **over) -> dict:
    """A seeded float32 minicpm_sala checkpoint, written as the benchmark
    writes its own (tensor names from benchmarks/reference/minicpm_sala.py)."""
    from benchmarks.harness.weights import write_checkpoint

    cfg = tiny_minicpm_sala_config(**over)
    write_checkpoint(Path(model_dir), cfg, seed=seed, dtype="float32")
    return cfg


def rehearsal_config(name: str, **over) -> dict:
    """The benchmark configuration `name` at its rehearsal size, the HF keys
    alone: the file's top-level keys less the benchmark's own, its
    `rehearse.config` over them, then `over`."""
    import json

    root = Path(__file__).resolve().parents[2]
    full = json.loads((root / "benchmarks" / "configs" / f"{name}.json").read_text())
    cfg = {k: v for k, v in full.items()
           if k not in ("assumed", "deployment", "serve", "check", "rehearse")}
    return {**cfg, **full["rehearse"]["config"], **over}


def tiny_mellum_config(**over) -> dict:
    """`mellum2-12b-a2.5b-8l` at its rehearsal size: hidden 64, 8 query / 2
    KV heads of 16, 8 experts of 32 top-2, two periods of (sliding x 3,
    full), window 8, YaRN x16 over an original length of 32 on the full kind."""
    return rehearsal_config("mellum2-12b-a2.5b-8l", **over)


def make_tiny_mellum(model_dir: str | Path, seed: int = 2**31 + 54, **over) -> dict:
    """A seeded float32 mellum checkpoint, written as the benchmark writes
    its own (tensor names from benchmarks/reference/mellum.py)."""
    from benchmarks.harness.weights import write_checkpoint

    cfg = tiny_mellum_config(**over)
    write_checkpoint(Path(model_dir), cfg, seed=seed, dtype="float32")
    return cfg
