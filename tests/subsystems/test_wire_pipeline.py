"""Overlapped quantized wire pipeline (DNET_WIRE_PIPELINE=1).

Covers the codec units (launch/finalize parity with the synchronous
encoders, the per-tensor qsparse8 fallback), the EncodeRing backpressure
contract, the chaos points, the PR 4 dedup/resume interaction (a stream
re-open re-sends the ENCODED frame with its original seq), the sched
tick-dispatch seam, and the acceptance parity tests: byte-identical greedy
SSE legacy-vs-pipelined with the lossless codec, and tolerance-based token
parity for the qsparse8 hop codec — both through the REAL HTTP server over
the in-process two-shard ring (loadgen/ring_harness.py).
"""

import asyncio
import os
import re

import numpy as np
import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.obs import metric

pytestmark = [pytest.mark.ring, pytest.mark.shard]


@pytest.fixture(autouse=True)
def _wire_env():
    """Every test leaves the wire env exactly as it found it."""
    keys = ("DNET_WIRE_PIPELINE", "DNET_WIRE_CODEC", "DNET_WIRE_QSPARSE_PCT",
            "DNET_WIRE_DEPTH")
    saved = {k: os.environ.get(k) for k in keys}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    reset_settings_cache()


# ---------------------------------------------------------------------------
# codec units: launch/finalize parity + per-tensor fallback
# ---------------------------------------------------------------------------


def test_launch_encode_lossless_matches_tensor_to_bytes():
    import jax.numpy as jnp

    from dnet_tpu.compression import launch_encode
    from dnet_tpu.utils.serialization import tensor_to_bytes

    x = np.random.default_rng(0).normal(size=(1, 7, 64)).astype(np.float32)
    enc = launch_encode(jnp.asarray(x), 0.0, wire_dtype="bfloat16")
    payload, dtype, shape = tensor_to_bytes(x, "bfloat16")
    assert enc.dtype == dtype and enc.shape == shape
    assert enc.finalize() == payload  # byte-identical: the parity anchor


def test_launch_encode_sparse_matches_compress_tensor():
    import jax.numpy as jnp

    from dnet_tpu.compression import compress_tensor, launch_encode

    x = np.random.default_rng(1).normal(size=(1, 3, 128)).astype(np.float32)
    enc = launch_encode(jnp.asarray(x), 0.5, wire_dtype="bfloat16",
                        quant_bits=0)
    payload, dtype, shape = compress_tensor(x, 0.5, wire_dtype="bfloat16",
                                            quant_bits=0)
    assert enc.dtype == dtype and enc.shape == shape
    assert enc.finalize() == payload


def test_launch_encode_q8_value_parity_and_roundtrip():
    """The jitted q8 encode may differ from the eager host path by one ULP
    in a scale (reduction order), so parity is checked on the DECODED
    values; the payload must still round-trip through both decoders."""
    import jax.numpy as jnp

    from dnet_tpu.compression import (
        compress_tensor,
        decompress_tensor,
        decompress_tensor_device,
        launch_encode,
    )

    x = np.random.default_rng(2).normal(size=(2, 2, 128)).astype(np.float32)
    enc = launch_encode(jnp.asarray(x), 0.5, wire_dtype="float32",
                        quant_bits=8, group_size=64)
    p_host, dtype, shape = compress_tensor(x, 0.5, wire_dtype="float32",
                                           quant_bits=8, group_size=64)
    assert enc.dtype == dtype and enc.shape == shape
    p_dev = enc.finalize()
    a = decompress_tensor(p_dev, dtype, shape).astype(np.float32)
    b = decompress_tensor(p_host, dtype, shape).astype(np.float32)
    np.testing.assert_allclose(a, b, atol=1e-4)
    c = np.asarray(decompress_tensor_device(p_dev, dtype, shape), np.float32)
    np.testing.assert_allclose(a, c, atol=1e-6)


def test_q8_per_tensor_fallback_roundtrip():
    """A frame with fewer kept columns than one quant group carries ONE
    per-tensor f32 scale/bias pair (gs=0 tag) instead of zero-padded group
    grids, and both decoders honor it."""
    from dnet_tpu.compression import (
        compress_tensor,
        decompress_tensor,
        decompress_tensor_device,
    )

    x = np.random.default_rng(3).normal(size=(1, 4, 32)).astype(np.float32)
    payload, dtype, shape = compress_tensor(x, 0.5, wire_dtype="float32",
                                            quant_bits=8, group_size=64)
    assert "|gs=0" in dtype
    # bitmask (4B for D=32) + codes (4*16) + ONE scale + ONE bias
    assert len(payload) == 4 + 4 * 16 + 4 + 4
    host = decompress_tensor(payload, dtype, shape).astype(np.float32)
    dev = np.asarray(decompress_tensor_device(payload, dtype, shape), np.float32)
    np.testing.assert_allclose(host, dev, atol=1e-6)
    # kept columns reconstruct within int8-affine error of the original
    mask = host.reshape(-1, 32) != 0
    err = np.abs((host - x).reshape(-1, 32)[mask])
    span = x.max() - x.min()
    assert err.max() <= span / 255.0 + 1e-5


def test_q8_grouped_path_keeps_gs_tag():
    from dnet_tpu.compression import compress_tensor, decompress_tensor

    x = np.random.default_rng(4).normal(size=(1, 2, 256)).astype(np.float32)
    payload, dtype, shape = compress_tensor(x, 0.5, wire_dtype="float32",
                                            quant_bits=8, group_size=64)
    assert "|gs=64" in dtype
    out = decompress_tensor(payload, dtype, shape)
    assert out.shape == x.shape


# ---------------------------------------------------------------------------
# EncodeRing backpressure + chaos points
# ---------------------------------------------------------------------------


def test_encode_ring_depth_bounds_and_release():
    from dnet_tpu.transport.wire_pipeline import EncodeRing

    ring = EncodeRing(depth=2)
    assert ring.acquire() and ring.acquire()
    assert ring.inflight == 2
    # full: the third acquire times out (backpressure) without deadlock
    assert ring.acquire(max_wait_s=0.05) is False
    ring.release()
    assert ring.acquire(max_wait_s=0.05) is True
    ring.release()
    ring.release()
    assert ring.inflight == 0


def test_pending_payload_discard_releases_slot():
    from dnet_tpu.compression import launch_encode
    from dnet_tpu.transport.wire_pipeline import EncodeRing, PendingWirePayload

    ring = EncodeRing(depth=1)
    assert ring.acquire()
    enc = launch_encode(np.zeros((1, 1, 8), np.float32), 0.0)
    pending = PendingWirePayload(enc, ring=ring)
    pending.discard()  # dropped frame (outq overflow): slot must free
    assert ring.inflight == 0
    assert ring.acquire(max_wait_s=0.05) is True
    ring.release()


def test_chaos_wire_encode_error_still_releases_slot():
    from dnet_tpu.compression import launch_encode
    from dnet_tpu.resilience import chaos
    from dnet_tpu.transport.wire_pipeline import EncodeRing, PendingWirePayload

    before = metric("dnet_chaos_injected_total").labels(
        point="wire_encode").value
    chaos.install_chaos("wire_encode:error_at:1")
    try:
        ring = EncodeRing(depth=1)
        assert ring.acquire()
        enc = launch_encode(np.zeros((1, 1, 8), np.float32), 0.0)
        pending = PendingWirePayload(enc, ring=ring)
        with pytest.raises(chaos.ChaosError):
            pending.finalize()
        # the failed encode must not leak its ring slot
        assert ring.inflight == 0
        assert metric("dnet_chaos_injected_total").labels(
            point="wire_encode").value == before + 1
    finally:
        chaos.clear_chaos()


def test_chaos_wire_decode_fails_frame_at_ingress(tiny_llama_dir):
    """An injected wire_decode fault at ingress NACKs the frame (the exact
    path a corrupt payload would take) instead of reaching compute."""
    from dnet_tpu.resilience import chaos
    from dnet_tpu.shard.adapter import RingAdapter
    from dnet_tpu.shard.runtime import ShardRuntime
    from dnet_tpu.transport.protocol import ActivationFrame
    from dnet_tpu.utils.serialization import tensor_to_bytes
    from tests.fakes.transport import FakeCallbackClient, FakeRingClient

    os.environ["DNET_WIRE_PIPELINE"] = "1"
    reset_settings_cache()

    async def go():
        rt = ShardRuntime("solo")
        adapter = RingAdapter(
            rt,
            ring_client_factory=lambda addr: FakeRingClient(addr),
            callback_client_factory=lambda addr: FakeCallbackClient(addr),
        )
        loop = asyncio.get_running_loop()
        rt.start(loop)
        await adapter.start()
        await loop.run_in_executor(
            None,
            lambda: rt.load_model_core(
                str(tiny_llama_dir), [2, 3], max_seq=64,
                param_dtype="float32",
            ),
        )
        try:
            hidden = np.zeros((1, 1, 64), np.float32)
            payload, dtype, shape = tensor_to_bytes(hidden, "bfloat16")
            frame = ActivationFrame(
                nonce="cz", seq=0, layer_id=1, pos=0, dtype=dtype,
                shape=shape, payload=payload,
            )
            chaos.install_chaos("wire_decode:error_at:1")
            ok, msg = await adapter.ingress_frame(frame)
            assert not ok and "wire decode failed" in msg
        finally:
            chaos.clear_chaos()
            await adapter.shutdown()
            rt.stop()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# dedup/resume interaction: the re-send is the ENCODED frame, original seq
# ---------------------------------------------------------------------------


def test_stream_reopen_resends_encoded_frame_with_original_seq(tiny_llama_dir):
    """PR 4 contract under the pipeline: the frame is finalized to bytes
    BEFORE the first send attempt, so a broken-stream re-open re-sends the
    identical encoded payload with the identical seq (the receiver's
    (nonce, seq, layer_id) dedup then works on real bytes)."""
    from dnet_tpu.shard.adapter import RingAdapter
    from dnet_tpu.shard.runtime import ShardRuntime
    from dnet_tpu.transport.protocol import ActivationFrame, StreamAck
    from dnet_tpu.utils.serialization import tensor_to_bytes
    from tests.fakes.transport import FakeCallbackClient, FakeRingClient, FakeStreamCall

    os.environ["DNET_WIRE_PIPELINE"] = "1"
    reset_settings_cache()
    attempts = []

    class BreakOnceClient(FakeRingClient):
        def open_stream(self):
            async def deliver(frame):
                attempts.append(frame)
                if len(attempts) == 1:
                    raise ConnectionError("stream snapped mid-write")
                return StreamAck(nonce=frame.nonce, seq=frame.seq, ok=True)

            call = FakeStreamCall(deliver)
            self.streams.append(call)
            return call

    async def go():
        rt = ShardRuntime("head")
        adapter = RingAdapter(
            rt,
            ring_client_factory=lambda addr: BreakOnceClient(addr),
            callback_client_factory=lambda addr: FakeCallbackClient(addr),
        )
        loop = asyncio.get_running_loop()
        rt.start(loop)
        await adapter.start()
        await loop.run_in_executor(
            None,
            lambda: rt.load_model_core(
                str(tiny_llama_dir), [0, 1], max_seq=64,
                param_dtype="float32",
            ),
        )
        adapter.configure_topology("next:1")
        try:
            ids = np.asarray([[5, 7, 9]], dtype=np.int32)
            payload, _dt, shape = tensor_to_bytes(ids)
            frame = ActivationFrame(
                nonce="rs", seq=4, layer_id=-1, pos=0, dtype="tokens",
                shape=shape, payload=payload, callback_url="grpc://api:1",
            )
            ok, _ = await adapter.ingress_frame(frame)
            assert ok
            t0 = asyncio.get_event_loop().time()
            while len(attempts) < 2:
                await asyncio.sleep(0.01)
                assert asyncio.get_event_loop().time() - t0 < 15
            first, second = attempts[0], attempts[1]
            assert first.seq == second.seq == 4
            assert isinstance(second.payload, bytes)
            assert first.payload == second.payload  # the ENCODED bytes
            assert first.dtype == second.dtype == "bfloat16"
        finally:
            await adapter.shutdown()
            rt.stop()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# sched tick dispatch through the pipeline seam
# ---------------------------------------------------------------------------


def test_execute_tick_launches_every_chunk_before_the_decode_read():
    """The order inside a tick (sched/step.py), with step n in flight from
    the tick before: every chunk launched and a completed prompt's adoption
    enqueued, then step n+1 LAUNCHED, chained to step n; only THEN step n
    read, its results leaving through on_decode before any first token is
    read.  And a tick whose chunk runs on past it launches no step behind
    a step in flight."""
    import dataclasses

    import numpy as np

    from dnet_tpu.core.types import DecodingParams
    from dnet_tpu.sched.policy import TickPlan
    from dnet_tpu.sched.step import execute_tick
    from tests.subsystems.test_sched import FakeStepEngine, _chunk

    order = []

    class OnDevice:
        """Stands in for an adopted first token still on the device: the
        tick's read (jax.device_get) is the only thing that converts it."""

        def __init__(self, nonce):
            self.nonce = nonce

        def __array__(self, dtype=None, copy=None):
            order.append(("first_token_read", self.nonce))
            return np.zeros(1, np.int32)

    def tracked(name, fn, what=lambda *a: a[0]):
        def call(*a, **k):
            order.append((name, what(*a, **k)))
            return fn(*a, **k)

        return call

    eng = FakeStepEngine()
    eng.occupy("dec", committed=4, blocks=1)
    eng.decode_launch = tracked(
        "decode_launch", eng.decode_launch,
        lambda r, budgets=None, chain=None: (sorted(r), sorted(chain.order)),
    )
    eng.decode_read = tracked("decode_read", eng.decode_read, lambda f, **k: sorted(f.order))
    eng.prefill_chunk = tracked("prefill", eng.prefill_chunk)
    eng.adopt_prefilled = tracked("adopt", lambda n, logits, dec: OnDevice(n))
    plan = TickPlan()
    plan.decode = {"dec": (42, DecodingParams())}
    plan.steps = {"dec": 3}
    plan.budgets = {"dec": 9}
    first = execute_tick(eng, plan)  # nothing in flight yet: launch, no read
    assert order == [("decode_launch", (["dec"], []))] and not first.decode_results
    del order[:]
    plan.prefills = [_chunk("new", last=False), _chunk("done")]
    res = execute_tick(
        eng, plan, on_decode=lambda n, s: order.append(("on_decode", n)),
        follows=first,
    )
    assert order == [
        ("prefill", "new"),
        ("prefill", "done"),
        ("adopt", "done"),
        ("decode_launch", (["dec"], ["dec"])),
        ("decode_read", ["dec"]),
        ("on_decode", "dec"),
        ("first_token_read", "done"),
    ]
    assert res.flight.chained == {"dec"} and res.flight is not first.flight
    assert res.dispatched == ["dec"]
    # a tick that leaves a chunk running behind the step in flight (it
    # adopts nothing, so it does not wait the chunk out) launches no step:
    # the chunk is what the device runs across the host's turn
    del order[:]
    plan.prefills = [_chunk("long", last=False)]
    held = execute_tick(eng, plan, follows=res)
    assert order == [("prefill", "long"), ("decode_read", ["dec"])]
    assert held.flight is None and set(held.decode_results) == {"dec"}
    del order[:]
    plan.prefills = [dataclasses.replace(plan.prefills[0], first=False)]
    after = execute_tick(eng, plan, follows=held)  # none in flight: chunk, then the step
    assert order == [("prefill", "long"), ("decode_launch", (["dec"], []))]
    assert list(after.flight.order) == ["dec"] and not after.decode_results
    assert "dec" in res.decode_results  # still in the barriered result too
    assert isinstance(res.adopted["done"], np.ndarray)  # host data only


def test_sched_pipeline_parity_and_no_double_resolve(tiny_llama_dir, monkeypatch):
    """The scheduler hands decode results off mid-tick whenever the plan
    has chunks, whatever DNET_WIRE_PIPELINE says (its other readers are
    shard/compute.py and transport/): decode futures resolve through the
    early-dispatch bridge, the barriered apply skips them, and the burst's
    greedy texts are the same with the switch on."""
    from tests.subsystems.test_sched import _serve_burst

    prompts = ["Hi", "Hello there", "A quick brown fox", "tail prompt"]
    plain = asyncio.run(_serve_burst(tiny_llama_dir, prompts, sched=True))
    os.environ["DNET_WIRE_PIPELINE"] = "1"
    reset_settings_cache()
    piped = asyncio.run(_serve_burst(tiny_llama_dir, prompts, sched=True))
    reset_settings_cache()
    assert piped == plain


# ---------------------------------------------------------------------------
# acceptance: in-process two-shard ring through the REAL HTTP server
# ---------------------------------------------------------------------------


def _normalize_sse(raw: str) -> str:
    raw = re.sub(r'"id": ?"[^"]*"', '"id": "chatcmpl-X"', raw)
    return re.sub(r'"created": ?\d+', '"created": 0', raw)


async def _ring_sse(model_dir, prompts, wire_codec="", max_tokens=6,
                    stream=True):
    from aiohttp.test_utils import TestClient, TestServer

    from dnet_tpu.loadgen.ring_harness import InprocRing

    ring = InprocRing(str(model_dir), wire_codec=wire_codec)
    await ring.start()
    try:
        client = TestClient(TestServer(ring.app))
        await client.start_server()
        try:
            out = []
            for p in prompts:
                resp = await client.post(
                    "/v1/chat/completions",
                    json={
                        "model": "inproc-ring",
                        "messages": [{"role": "user", "content": p}],
                        "max_tokens": max_tokens,
                        "temperature": 0,
                        "stream": stream,
                    },
                )
                assert resp.status == 200, await resp.text()
                if stream:
                    out.append((await resp.read()).decode())
                else:
                    body = await resp.json()
                    out.append(body["choices"][0]["message"]["content"])
            return out, ring.stats.as_dict()
        finally:
            await client.close()
    finally:
        await ring.stop()


@pytest.mark.http
def test_pipeline_lossless_sse_byte_parity(tiny_llama_dir):
    """ACCEPTANCE: DNET_WIRE_PIPELINE=1 with the lossless codec keeps
    greedy SSE streams byte-identical vs the legacy send path, through the
    real HTTP server over a real two-shard ring."""
    prompts = ["Hi", "Hello there", "A quick brown"]
    os.environ.pop("DNET_WIRE_PIPELINE", None)
    reset_settings_cache()
    legacy, legacy_stats = asyncio.run(_ring_sse(tiny_llama_dir, prompts))
    os.environ["DNET_WIRE_PIPELINE"] = "1"
    reset_settings_cache()
    enc_before = metric("dnet_wire_encode_ms").count
    piped, piped_stats = asyncio.run(_ring_sse(tiny_llama_dir, prompts))
    assert [_normalize_sse(s) for s in piped] == [
        _normalize_sse(s) for s in legacy
    ]
    for s in piped:  # real streams, not error shortcuts
        events = [ln for ln in s.splitlines() if ln.startswith("data: ")]
        assert events[-1] == "data: [DONE]" and len(events) > 2
    # identical wire: same hidden-hop bytes, same lossless codec tag
    assert piped_stats["hidden_bytes"] == legacy_stats["hidden_bytes"]
    assert list(piped_stats["by_codec"]) == ["bfloat16"]
    # the pipeline actually ran: encodes were observed and overlapped
    assert metric("dnet_wire_encode_ms").count > enc_before
    assert metric("dnet_wire_overlap_ratio").value > 0
    assert metric("dnet_wire_bytes_total").labels(dir="tx").value > 0
    assert metric("dnet_wire_bytes_total").labels(dir="rx").value > 0


@pytest.mark.http
def test_pipeline_qsparse8_token_parity_tolerance(tiny_llama_dir):
    """ACCEPTANCE: the qsparse8 hop codec under the pipeline — pure-int8
    working point (pct=0) — serves the seeded prompts to completion with
    tolerance-level token parity vs the lossless ring, at strictly fewer
    inter-hop bytes.  (The 64-dim random-weight fixture is hypersensitive
    to column dropping; byte-reduction at pct>0 is proven by the units
    above.)"""
    prompts = ["Hi", "Hello there", "A quick brown"]
    os.environ["DNET_WIRE_PIPELINE"] = "1"
    os.environ["DNET_WIRE_QSPARSE_PCT"] = "0.0"
    reset_settings_cache()
    ref, ref_stats = asyncio.run(
        _ring_sse(tiny_llama_dir, prompts, wire_codec="lossless",
                  max_tokens=8, stream=False)
    )
    got, q8_stats = asyncio.run(
        _ring_sse(tiny_llama_dir, prompts, wire_codec="qsparse8",
                  max_tokens=8, stream=False)
    )
    # every request completed, and the streams agree within tolerance
    assert len(got) == len(prompts)
    agree = sum(a == b for a, b in zip(ref, got))
    assert agree >= 2, (ref, got)
    # the quantized wire is strictly smaller and tagged as qsparse8
    assert list(q8_stats["by_codec"]) == ["qsparse8_v1"]
    assert (
        q8_stats["hidden_bytes"]["s0->s1"]
        < ref_stats["hidden_bytes"]["s0->s1"]
    )
    # same number of hidden hops — the codec shrank frames, not the ring
    assert (
        q8_stats["hidden_frames"]["s0->s1"]
        == ref_stats["hidden_frames"]["s0->s1"]
    )


def test_wire_codec_auto_resolution():
    """The ring manager's auto codec: qsparse8 only for hops that cross
    hosts; same-host, loopback, and single-shard rings stay lossless."""
    from dnet_tpu.api.ring_manager import RingModelManager
    from dnet_tpu.core.types import DeviceInfo

    def dev(host):
        return DeviceInfo(instance=host, host=host, http_port=1, grpc_port=2)

    a, b = dev("10.0.0.1"), dev("10.0.0.2")
    local = dev("127.0.0.1")
    assert RingModelManager._hop_codec(a, b, 2) == "qsparse8"
    assert RingModelManager._hop_codec(a, a, 2) == "lossless"
    assert RingModelManager._hop_codec(local, dev("localhost"), 2) == "lossless"
    assert RingModelManager._hop_codec(a, b, 1) == "lossless"
    os.environ["DNET_WIRE_CODEC"] = "lossless"
    reset_settings_cache()
    assert RingModelManager._hop_codec(a, b, 2) == "lossless"
    os.environ["DNET_WIRE_CODEC"] = "qsparse8"
    reset_settings_cache()
    assert RingModelManager._hop_codec(a, a, 2) == "qsparse8"
