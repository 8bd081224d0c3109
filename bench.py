"""Headline benchmark: SERVED decode tokens/sec on the flagship model, real TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

The primary metric is the serving path — the same hot loop that backs
/v1/chat/completions: LocalEngine (chunked lax.scan decode) behind
LocalAdapter + InferenceManager, with detokenization, SSE chunk assembly,
per-request metrics, and the per-chunk host round-trip all included
(BASELINE.md declares "decode tokens/sec ... via /v1/chat/completions" as
the metric; round 1 measured only a fused microbenchmark).  A fused-scan
microbenchmark still runs for reference — `serve_vs_fused` reports how much
of the pure-device rate the served path keeps.

Config: Llama-3.2-1B-class (first BASELINE.md config), int8 weight-only
quantized (the serving configuration — pass --bf16 for unquantized),
synthetic weights (zero-egress: no checkpoint downloads), batch 1, greedy.
vs_baseline is the fraction of the single-chip HBM-bandwidth roofline
(weights read once per step: bound = batch * HBM_BW / weights_bytes).
"""

from __future__ import annotations

import json
import statistics
import sys
import time


def _measure_fused(model, window, edge, kv, batch: int, n_steps: int = 64) -> float:
    """Pure-device ceiling: greedy decode fused into one lax.scan program."""
    import jax
    import jax.numpy as jnp

    def decode_step(window_params, edge_params, token, kv, pos):
        x = model.embed(edge_params, token)
        x, kv = model.apply_window(window_params, x, kv, pos)
        x = model.normalize(edge_params, x)
        logits = model.lm_project(edge_params, x)[:, 0]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kv

    def decode_scan(window_params, edge_params, token, kv, pos0):
        def body(carry, _):
            tok, kv, pos = carry
            tok, kv = decode_step(window_params, edge_params, tok, kv, pos)
            return (tok[:, None], kv, pos + 1), tok

        (_, kv, _), toks = jax.lax.scan(body, (token, kv, pos0), None, length=n_steps)
        return toks, kv

    step = jax.jit(decode_scan, donate_argnums=(3,))
    token = jnp.ones((batch, 1), dtype=jnp.int32)
    toks, kv = step(window, edge, token, kv, jnp.int32(0))  # warmup/compile
    toks.block_until_ready()
    # best-of-2 timed windows: the ceiling is the denominator of
    # serve_vs_fused, and a one-shot window swings +/-6% under shared-CPU
    # scheduling (r4's apparent 0.93 -> 0.86 "regression" was exactly this)
    best = 0.0
    pos = n_steps
    for _ in range(2):
        t0 = time.perf_counter()
        toks, kv = step(window, edge, token, kv, jnp.int32(pos))
        toks.block_until_ready()
        best = max(best, batch * n_steps / (time.perf_counter() - t0))
        pos += n_steps
    return best


def _measure_fused_chunks(engine, batch: int, n_steps: int = 256) -> float:
    """Pure-device ceiling for chunk-capable engines (mesh): back-to-back
    decode_chunk dispatch/read with no serving stack in the loop.  TWO warm
    calls (the second chunk still recompiles: the donated KV layout changes
    after the first) and >= 8 timed chunks, so a stray compile cannot
    dominate the window and understate the ceiling."""
    from dnet_tpu.core.types import DecodingParams

    dec = DecodingParams(temperature=0.0)
    engine.prefill("__fused__", [1, 2, 3, 4], seed=0)
    engine.decode_chunk("__fused__", 1, dec, 32)  # compile
    engine.decode_chunk("__fused__", 1, dec, 32)  # steady-state layout
    t0 = time.perf_counter()
    done = 0
    while done < n_steps:
        done += len(engine.decode_chunk("__fused__", 1, dec, 32))
    dt = time.perf_counter() - t0
    engine.end_session("__fused__")
    return batch * done / dt


def _measure_served(engine, batch: int) -> dict:
    """The declared metric: decode tok/s + TTFT through the serving stack."""
    import asyncio

    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.schemas import ChatCompletionRequest
    from dnet_tpu.api.strategies import LocalAdapter
    from dnet_tpu.utils.tokenizer import ByteTokenizer

    class BenchTokenizer(ByteTokenizer):
        @property
        def eos_token_ids(self) -> set[int]:
            # unreachable id: random-weight greedy decode must never stop
            # early, so every request generates exactly max_tokens tokens
            return {-1}

    adapter = LocalAdapter(engine, chunk_size=32)
    manager = InferenceManager(adapter, request_timeout_s=600.0)
    manager.tokenizer = BenchTokenizer()
    manager.model_id = "bench"

    # 1 (prefill) + ramp 2+4+8+16 + eight full 32-chunks: long enough that
    # steady-state chunked decode dominates the ramp-up
    max_tokens = 287
    req = ChatCompletionRequest.model_validate(
        {
            "model": "bench",
            "messages": [{"role": "user", "content": "Benchmark the decode path."}],
            "max_tokens": max_tokens,
            "temperature": 0.0,
            "profile": True,
        }
    )

    async def run() -> dict:
        await adapter.start()
        metrics = []
        prompt_tokens = 0
        for i in range(4):  # request 0 is the compile warmup
            r = await manager.generate(req)
            if i == 0:
                # drop the warmup's compile-inflated observations so the
                # registry percentiles (_obs_snapshot) cover exactly the
                # timed requests, matching the medians computed below
                from dnet_tpu.obs import reset_obs

                reset_obs()
            if i > 0:
                assert r.usage.completion_tokens == max_tokens, (
                    f"expected {max_tokens} tokens, got {r.usage.completion_tokens}"
                )
                metrics.append(r.metrics)
                prompt_tokens = r.usage.prompt_tokens
        await adapter.shutdown()
        return {
            "tok_s": statistics.median(m.tps_decoding for m in metrics),
            "ttft_p50_ms": statistics.median(m.ttfb_ms for m in metrics),
            # mean live context during decode, for the MFU attention term
            "mean_ctx": prompt_tokens + max_tokens // 2,
        }

    return asyncio.run(run())


def _obs_snapshot() -> dict:
    """Histogram percentiles from the obs registry, merged into the emitted
    JSON line.  The served measurement runs through the real InferenceManager
    stack, so the registry's dnet_decode_step_ms / dnet_ttft_ms series
    already hold every step of the timed section — the artifact gains
    distribution shape (p50/p95) on top of the medians for free."""
    from dnet_tpu.obs import get_registry

    out: dict = {}
    for name, key in (
        ("dnet_decode_step_ms", "decode_step"),
        ("dnet_ttft_ms", "ttft"),
        ("dnet_prefill_ms", "prefill"),
    ):
        h = get_registry().get(name)
        if h is None or h.count == 0:
            continue
        out[f"{key}_p50_ms"] = round(h.percentile(0.5), 3)
        out[f"{key}_p95_ms"] = round(h.percentile(0.95), 3)
        out[f"{key}_n"] = int(h.count)
    return out


def main() -> None:
    from dnet_tpu.config import configure_compile_cache

    configure_compile_cache()
    import jax

    # measured in this process, on the chip: a backend other than tpu is an
    # error unless --smoke asked for the code-path check at toy shapes
    if jax.default_backend() != "tpu" and "--smoke" not in sys.argv:
        print(
            f"error: backend is {jax.default_backend()!r}, not 'tpu' "
            "(--smoke runs the toy-shape code-path check on any backend)",
            file=sys.stderr,
        )
        raise SystemExit(1)
    import jax.numpy as jnp

    from dnet_tpu.core.kvcache import init_cache
    from dnet_tpu.models.base import ModelConfig
    from dnet_tpu.models.llama import LlamaRingModel
    from dnet_tpu.utils.random_init import LLAMA_3_2_1B_CONFIG, random_llama_params

    bits = 0 if "--bf16" in sys.argv else (4 if "--int4" in sys.argv else 8)
    batch = 1
    if "--batch" in sys.argv:  # aggregate throughput: N sequences per step
        try:
            batch = int(sys.argv[sys.argv.index("--batch") + 1])
        except (IndexError, ValueError):
            print(json.dumps({"error": "--batch requires an integer"}))
            raise SystemExit(2)
        if batch < 1:
            print(json.dumps({"error": "--batch must be >= 1"}))
            raise SystemExit(2)
    cfg_dict = dict(LLAMA_3_2_1B_CONFIG)
    if "--smoke" in sys.argv:  # tiny shapes: code-path validation on CPU
        cfg_dict.update(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, head_dim=16,
        )
    cfg = ModelConfig.from_hf({**cfg_dict, "architectures": []})
    layers = list(range(cfg.num_hidden_layers))
    model = LlamaRingModel(cfg, layers)
    window, edge = random_llama_params(cfg, layers, dtype="bfloat16")
    if bits:
        import numpy as _np

        from dnet_tpu.ops.quant import QUANTIZABLE, quantize_tree

        # smoke shapes have tiny contraction dims: a smaller scale-group
        # keeps groups divisible across tp ranks in --mesh mode
        group = 32 if "--smoke" in sys.argv else 0
        window = quantize_tree(
            {k: _np.asarray(v) for k, v in window.items()}, QUANTIZABLE,
            bits=bits, group_size=group,
        )
        edge = model.quantize_edge(edge, bits, group_size=group)
    # device-resident: leaving numpy here would re-upload every step
    window = jax.tree.map(jnp.asarray, window)
    edge = jax.tree.map(jnp.asarray, edge)
    max_seq = 1024

    flash_dec = _flash_decode_microbench()

    mesh_cfg = None
    if "--mesh" in sys.argv:  # e.g. --mesh 2x2 = pp2/tp2 over local devices
        try:
            pp_s, tp_s = sys.argv[sys.argv.index("--mesh") + 1].split("x")
            mesh_cfg = (int(pp_s), int(tp_s))
        except (IndexError, ValueError):
            print(json.dumps({"error": "--mesh requires PPxTP, e.g. 2x2"}))
            raise SystemExit(2)

    if mesh_cfg is not None:
        from dnet_tpu.parallel.engine import MeshEngine

        pp_n, tp_n = mesh_cfg
        engine = MeshEngine.from_params(
            cfg, window, edge, pp=pp_n, tp=tp_n, batch=batch, max_seq=max_seq,
        )
        fused_tok_s = _measure_fused_chunks(engine, batch)
        served = _measure_served(engine, batch)
    else:
        from dnet_tpu.core.engine import LocalEngine

        kv = init_cache(model.kv_config(len(layers), batch, max_seq, "bfloat16"))
        fused_tok_s = _measure_fused(model, window, edge, kv, batch)
        engine = LocalEngine.from_params(
            cfg, window, edge, batch=batch, max_seq=max_seq
        )
        served = _measure_served(engine, batch)
    obs_stats = _obs_snapshot()  # registry state right after the timed section
    tok_s = batch * served["tok_s"]  # tps_decoding is per-lane; lanes decode together

    # single-chip HBM roofline for decode: read all weights per token
    param_bytes = sum(
        int(a.size) * a.dtype.itemsize
        for a in jax.tree.leaves((window, edge))
    )
    # --smoke measures a toy config: the metric name must say so (a smoke
    # number under the llama1b name would be an actively misleading artifact)
    model_tag = "smoke" if "--smoke" in sys.argv else "llama1b"
    if mesh_cfg is not None:
        metric = "served_decode_tok_s_%s_%s_mesh_pp%dtp%d" % (
            model_tag, {0: "bf16", 4: "int4", 8: "int8"}[bits],
            mesh_cfg[0], mesh_cfg[1],
        )
    else:
        metric = "served_decode_tok_s_%s_%s_1chip" % (
            model_tag, {0: "bf16", 4: "int4", 8: "int8"}[bits]
        )
    if batch > 1:
        metric += f"_b{batch}"
    dev = jax.devices()[0]
    on_accel = jax.default_backend() == "tpu"
    hbm_bw, peak_flops = CHIP_SPECS[_chip_gen(dev)] if on_accel else (0.0, 0.0)
    # weight-bound decode bound: weights are read once per STEP, so N batch
    # lanes share one read — the aggregate bound scales with batch; a mesh
    # splits the read across its chips (each reads only its shard)
    n_chips = mesh_cfg[0] * mesh_cfg[1] if mesh_cfg is not None else 1
    roofline = batch * n_chips * hbm_bw / param_bytes
    # the TPU HBM roofline is meaningless for a --smoke run off the chip:
    # re-base against this device's own fused-scan ceiling so the number
    # stays interpretable instead of printing noise like 0.0002
    if on_accel:
        vs_baseline = round(tok_s / roofline, 4)
        basis = "tpu_hbm_roofline"
    else:
        vs_baseline = round(tok_s / fused_tok_s, 4)
        basis = "own_fused_ceiling_cpu"
    # MFU: model FLOPs/token from the config (2 MACs per weight in every
    # matmul + the two attention matmuls over the mean live context of the
    # served run), against the chip generation's bf16 peak on TPU — or
    # against THIS device's measured matmul rate under --smoke off the chip, so
    # the number never pretends a CPU run hit TPU silicon.  Decode is
    # HBM-bound, so single-chip decode MFU is expected to be small; the
    # point is roofline context the driver can judge, not a big number.
    fpt = _flops_per_token(cfg, mean_ctx=served["mean_ctx"])
    if on_accel:
        mfu = tok_s * fpt / (n_chips * peak_flops)
        mfu_basis = "chip_peak_bf16"
    else:
        from dnet_tpu.parallel.profiler import profile_device_quick

        # the forced-host "devices" of a CPU mesh share one host's cores,
        # and profile_device_quick already measures the whole host — no
        # per-chip multiply here
        mfu = tok_s * fpt / profile_device_quick()["flops_bf16"]
        mfu_basis = "measured_matmul_cpu"
    out = {
        "metric": metric,
        "value": round(tok_s, 2),
        "unit": "tok/s",
        "vs_baseline": vs_baseline,
        "vs_baseline_basis": basis,
        "fused_tok_s": round(fused_tok_s, 2),
        "serve_vs_fused": round(tok_s / fused_tok_s, 4),
        "ttft_p50_ms": round(served["ttft_p50_ms"], 1),
        "device": getattr(dev, "device_kind", "") or jax.default_backend(),
        "flops_per_token": int(fpt),
        "mfu": round(mfu, 6),
        "mfu_basis": mfu_basis,
    }
    out.update(flash_dec)
    out.update(obs_stats)
    if "--smoke" in sys.argv:
        out.update(_compress_microbench())
        if mesh_cfg is None:
            out.update(_spec_microbench(cfg, window, edge, max_seq))
    print(json.dumps(out))


def _flops_per_token(cfg, mean_ctx: int) -> float:
    """Model FLOPs per decoded token from the config alone (2 FLOPs per
    weight in every matmul — qkv/o/mlp per layer plus the lm head — and
    the two attention matmuls QK^T and PV over the mean live context).
    Independent of weight quantization: int8/int4 packing changes bytes
    read, not MACs performed.  Ref self-metrics analog:
    /root/reference/src/dnet/api/inference.py:216-233 (tokens/sec); this
    adds the FLOPs numerator the MFU judgment needs."""
    h = cfg.hidden_size
    H = cfg.num_attention_heads
    KVH = cfg.num_key_value_heads
    Hd = cfg.head_dim
    qkv = h * (H * Hd + 2 * KVH * Hd)
    o = H * Hd * h
    mlp = 3 * h * cfg.intermediate_size
    per_layer = 2 * (qkv + o + mlp) + 4 * mean_ctx * H * Hd
    lm_head = 2 * h * cfg.vocab_size
    return float(cfg.num_hidden_layers * per_layer + lm_head)


def _flash_decode_microbench() -> dict:
    """Long-cache decode attention: split-K Pallas kernel vs dense attend
    (TPU only — the kernel is ineligible on CPU).  A lowering error or a
    parity failure fails the benchmark: it never edits the program it
    measures."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        return {}
    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import flash_decode_attend, flash_decode_eligible

    B, H, KVH, Hd, S = 1, 32, 8, 128, 32768
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, 1, H, Hd), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, KVH, Hd), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, KVH, Hd), jnp.bfloat16)
    if not flash_decode_eligible(q, k):
        return {"flash_decode": "ineligible"}
    dense = jax.jit(lambda q, k, v, p: attend(q, k, v, mask=causal_mask(1, S, p)))
    kern = jax.jit(lambda q, k, v, p: flash_decode_attend(q, k, v, p))
    ref = np.asarray(dense(q, k, v, jnp.int32(S - 1)), np.float32)
    got = np.asarray(kern(q, k, v, jnp.int32(S - 1)), np.float32)
    err = float(np.max(np.abs(ref - got)))
    if err > 3e-2:  # bf16 long-sum tolerance; beyond it = miscompile
        raise RuntimeError(f"flash decode kernel off the dense op by {err}")
    out: dict = {"flash_decode_max_err": round(err, 5)}
    for tag, pos in (("p2k", 2047), ("full", S - 1)):
        for name, fn in (("dense", dense), ("kernel", kern)):
            fn(q, k, v, jnp.int32(pos)).block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(20):
                r = fn(q, k, v, jnp.int32(pos))
            r.block_until_ready()
            out[f"flash_decode_{name}_us_{tag}"] = round(
                (time.perf_counter() - t0) / 20 * 1e6, 1
            )
    return out


def _spec_microbench(cfg, window, edge, max_seq: int) -> dict:
    """Speculative decoding on a repetitive stream (smoke mode only): the
    verify-forward path emits 1..L+1 tokens per weight read, so accepted
    drafts multiply throughput; tokens/block records the acceptance rate
    the gain came from."""
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.core.types import DecodingParams

    # batch pinned to 1: speculation is a batch-1 feature (acceptance
    # length is per-lane; spec_eligible refuses larger batches), so this
    # number is per-stream regardless of the bench's --batch flag
    eng = LocalEngine.from_params(
        cfg, window, edge, batch=1, max_seq=max_seq, spec_lookahead=4
    )
    # a repeating prompt gives prompt-lookup something to look up
    ids = [1, 7, 3, 11] * 8
    dec = DecodingParams(temperature=0.0)
    eng.prefill_and_sample("warm", ids, dec)
    eng.decode_spec("warm", ids[-1], dec, 8)  # compile the verify block
    eng.decode_step("warm", ids[-1], dec)  # compile the budget<=1 fallback
    eng.end_session("warm")
    res = eng.prefill_and_sample("s", ids, dec)
    tok = int(res.token[0])
    t0 = time.perf_counter()
    emitted = blocks = 0
    while emitted < 128:
        out = eng.decode_spec("s", tok, dec, 128 - emitted)
        emitted += len(out)
        blocks += 1
        tok = int(out[-1].token[0])
    dt = time.perf_counter() - t0
    eng.end_session("s")
    out = {
        "spec_tok_s": round(emitted / dt, 2),
        "spec_tokens_per_block": round(emitted / blocks, 2),
    }

    # spec x continuous batching (r4): two repetitive lanes speculate
    # concurrently with per-lane acceptance — aggregate tok/s across lanes
    from dnet_tpu.core.batch import BatchedEngine

    beng = BatchedEngine.from_params(
        cfg, window, edge, slots=2, max_seq=max_seq, spec_lookahead=4
    )
    toks = {}
    for i in range(2):
        toks[i] = int(beng.prefill_and_sample(f"b{i}", ids, dec).token[0])

    def round_once() -> int:
        """One spec round; drains each lane's block IN ORDER so the stream
        stays real — toks[i] becomes the lane's LAST emitted token (the one
        whose hist/KV position matches the advanced pos)."""
        res, _ = beng.decode_batch(
            {f"b{i}": (toks[i], dec) for i in range(2)},
            budgets={f"b{i}": 64 for i in range(2)},
        )
        n_tok = 0
        for i in range(2):
            n = f"b{i}"
            rows = [res[n]] + beng._buffer.pop(n, [])
            toks[i] = int(rows[-1].token[0])
            n_tok += len(rows)
        return n_tok

    round_once()  # compile the verify block
    emitted = 0
    t0 = time.perf_counter()
    while emitted < 192:
        emitted += round_once()
    dt = time.perf_counter() - t0
    beng.end_session("b0")
    beng.end_session("b1")
    out["spec_batched_tok_s"] = round(emitted / dt, 2)
    return out


def _compress_microbench() -> dict:
    """DCN wire-format round-trip rates (smoke mode only).  The receive
    side is measured BOTH ways — host numpy decompress vs device-side
    dequant+scatter (the serving path) — so the artifact shows the
    receive-side improvement."""
    import jax
    import numpy as np

    from dnet_tpu.compression import (
        compress_tensor,
        decompress_tensor,
        decompress_tensor_device,
    )

    x = np.random.default_rng(0).normal(size=(1, 64, 2048)).astype(np.float32)
    out = {}
    for name, bits in (("sparse_v1", 0), ("qsparse8_v1", 8)):
        p, d, s = compress_tensor(x, 0.5, quant_bits=bits)  # warm
        jax.block_until_ready(decompress_tensor_device(p, d, s))  # compile
        t0 = time.perf_counter()
        for _ in range(5):
            p, d, s = compress_tensor(x, 0.5, quant_bits=bits)
            decompress_tensor(p, d, s)
        dt = (time.perf_counter() - t0) / 5
        t0 = time.perf_counter()
        for _ in range(5):
            decompress_tensor(p, d, s)
        host_ms = (time.perf_counter() - t0) / 5 * 1000
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(decompress_tensor_device(p, d, s))
        dev_ms = (time.perf_counter() - t0) / 5 * 1000
        out[f"{name}_roundtrip_ms"] = round(dt * 1000, 2)
        out[f"{name}_recv_host_ms"] = round(host_ms, 2)
        out[f"{name}_recv_device_ms"] = round(dev_ms, 2)
        out[f"{name}_ratio"] = round(x.nbytes / len(p), 2)
    return out


# one row per chip generation: (HBM bandwidth B/s, bf16 peak FLOP/s), keyed
# by a substring of jax's device_kind ("TPU v5 lite" is the v5e).  Source:
# Google Cloud TPU documentation, per-generation system architecture pages.
CHIP_SPECS = {
    "v6e": (1640e9, 918e12),
    "v6 lite": (1640e9, 918e12),
    "v5e": (819e9, 197e12),
    "v5 lite": (819e9, 197e12),
    "v5litepod": (819e9, 197e12),
    "v4": (1228e9, 275e12),
}


def _chip_gen(dev) -> str:
    kind = getattr(dev, "device_kind", "").lower()
    for gen in CHIP_SPECS:
        if gen in kind:
            return gen
    raise ValueError(
        f"device_kind {kind!r} has no row in CHIP_SPECS: add its published "
        "peaks rather than borrowing another chip's"
    )


if __name__ == "__main__":
    main()
