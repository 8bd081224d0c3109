"""Long-context (128K north star) proofs on CPU proxies.

The north star's long-context ring (Llama-3-70B, 128K) cannot run in this
image; what CAN be pinned down here is (a) the solver's KV memory model —
128K of KV per layer must displace resident layers and flip assignments to
weight-streaming, scaled by kv_bits — and (b) the sequence-parallel serving
path decoding correctly at the largest CPU-feasible context with quantized
KV (the same code path that shards 128K of KV across an sp axis on TPU).
"""

import numpy as np
import pytest

from dnet_tpu.core.types import DecodingParams, DeviceInfo
from dnet_tpu.parallel.solver import ModelProfile, solve_topology

pytestmark = pytest.mark.parallel


def _chip(name: str, hbm_gb: float) -> DeviceInfo:
    return DeviceInfo(
        instance=name, host="h", http_port=1, grpc_port=2,
        hbm_bytes=int(hbm_gb * 2**30), host_ram_bytes=256 * 2**30,
        flops_bf16=2e14, hbm_bw=8e11,
    )


def _llama70b_profile(seq_len: int, kv_bits: int = 0) -> ModelProfile:
    # 70B-class: 80 layers, ~0.9 GB/layer bf16, GQA 8 KV heads x 128 dim
    kvh, hd = 8, 128
    if kv_bits == 8:
        kv_bytes = 2 * kvh * (hd + 4)
    elif kv_bits == 4:
        kv_bytes = 2 * kvh * (hd // 2 + 4)
    else:
        kv_bytes = 2 * kvh * hd * 2
    return ModelProfile(
        model_id="llama-70b", num_layers=80,
        layer_bytes=int(0.9 * 2**30),
        layer_flops_per_token=2 * 0.9e9,
        kv_bytes_per_token_per_layer=kv_bytes,
        edge_bytes=2 * 2**30,
        seq_len=seq_len,
    )


def test_128k_kv_shifts_assignments_to_streaming():
    """At 4K context an 8-chip ring (10 layers/chip) holds everything
    resident; at 128K the per-layer KV (0.5 GB bf16) drops per-chip
    capacity below 10 and the solve must emit weight-streaming windows
    (residency < layers)."""
    devices = [_chip(f"c{i}", 16.0) for i in range(8)]
    short = solve_topology(devices, _llama70b_profile(4096))
    assert sum(short.solution["w"]) == 80
    assert all(
        a.residency_size == 0 for a in short.assignments
    ), "4K solve must be fully resident"

    long = solve_topology(devices, _llama70b_profile(131072))
    assert sum(long.solution["w"]) == 80
    streaming = [a for a in long.assignments if a.residency_size > 0]
    assert streaming, "128K KV must push at least one device to streaming"
    for a in streaming:
        assert 0 < a.residency_size < len(a.layers)
        assert a.window_size >= 1


def test_kv_bits_scale_the_128k_memory_pressure():
    """Quantized KV (8-bit) reclaims most of the 128K displacement: the
    int8 solve must keep strictly more layers resident than bf16."""
    devices = [_chip(f"c{i}", 16.0) for i in range(8)]
    bf16 = solve_topology(devices, _llama70b_profile(131072, kv_bits=0))
    int8 = solve_topology(devices, _llama70b_profile(131072, kv_bits=8), kv_bits=8)

    def resident(t):
        return sum(
            a.residency_size or len(a.layers) for a in t.assignments
        )

    assert resident(int8) > resident(bf16)
    assert int8.kv_bits == 8  # flows into ShardLoadModelRequest / engines


def test_sp_ring_decode_at_long_context(tiny_llama_dir, eight_devices):
    """Sequence-parallel serving at the largest CPU-feasible context:
    2048-token prefill with the KV sharded over sp=2 (1024 slots per rank)
    + int8-quantized KV, greedy decode parity vs single-device."""
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.parallel.engine import MeshEngine

    S = 2048
    rng = np.random.default_rng(11)
    ids = [int(x) for x in rng.integers(1, 250, size=S - 64)]  # ~97% of max
    dec = DecodingParams(temperature=0.0)

    local = LocalEngine(
        tiny_llama_dir, max_seq=S, param_dtype="float32", kv_quant_bits=8
    )
    want = [r.token_id for r in local.generate(ids, dec, max_tokens=8)]

    eng = MeshEngine(
        tiny_llama_dir, pp=2, tp=1, sp=2, max_seq=S, param_dtype="float32",
        kv_quant_bits=8,
    )
    got = [r.token_id for r in eng.generate(ids, dec, max_tokens=8)]
    assert got == want
