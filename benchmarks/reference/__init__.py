"""Plain references, one module per `model_type`, found by name.

Each exports `tensor_table(cfg)` (the HF tensor names and shapes the seeded
checkpoint holds) and `logits(model_dir, cfg, ids)` (the published forward
pass in plain float32 `jax.numpy`, no kernel, cache or batching)."""
