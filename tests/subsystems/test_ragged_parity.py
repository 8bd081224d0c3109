"""The paged pool attended in place, end-to-end: the interpret-mode kernel
— the REAL kernel logic, index-map clamping included — must serve
byte-identical greedy streams to the dense slot cache (BatchedEngine
kv_paged=False, the reference) through the production stack, under both
the scheduler and the legacy adapter, across the sharing edges the block
pool makes interesting (COW mid-block divergence, preemption -> resume
re-prefill, mid-block positions attended through clamped dead table
entries)."""

import asyncio
import re

import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import metric

pytestmark = pytest.mark.api


@pytest.fixture
def ragged_env(monkeypatch):
    """Small blocks + interpret-mode kernels: tier-1 CPU executes the
    actual Pallas program logic, not just the jnp twin."""
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    reset_settings_cache()
    yield
    reset_settings_cache()


def _normalize_sse(raw: str) -> str:
    """Strip the only run-specific bytes an SSE stream carries: the
    chatcmpl-<nonce> response id and the created wall-clock stamp."""
    raw = re.sub(r'"id": ?"[^"]*"', '"id": "chatcmpl-X"', raw)
    return re.sub(r'"created": ?\d+', '"created": 0', raw)


async def _sse_burst(model_dir, prompts, reference=None, max_tokens=6, slots=4):
    """The real HTTP server: load the tiny model, stream every prompt
    concurrently, return the raw SSE bytes per prompt.  `reference` None is
    a plain load (what serving_plan derives: the scheduler over the pool);
    an adapter class serves the DENSE engine through it instead, installed
    the way load_model's tail installs an engine."""
    from aiohttp.test_utils import TestClient, TestServer

    from dnet_tpu.api.http import ApiHTTPServer
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager

    inference = InferenceManager(
        adapter=None, request_timeout_s=120.0, max_concurrent=slots
    )
    manager = LocalModelManager(
        inference, max_seq=64, param_dtype="float32", batch_slots=slots
    )
    server = ApiHTTPServer(inference, manager)
    client = TestClient(TestServer(server.app))
    await client.start_server()
    try:
        if reference is None:
            r = await client.post("/v1/load_model", json={"model": str(model_dir)})
            assert r.status == 200, await r.text()
            assert manager.serving.adapter == "SchedulerAdapter"
            assert manager.engine.kv_pool is not None
        else:
            from dnet_tpu.utils.tokenizer import load_tokenizer

            eng = _engine(model_dir, paged=False, slots=8)
            inference.adapter = reference(eng)
            await inference.adapter.start()
            inference.tokenizer = load_tokenizer(model_dir)
            inference.model_id = "tiny"
            manager.engine = eng

        async def one(p):
            resp = await client.post(
                "/v1/chat/completions",
                json={
                    "model": "tiny",
                    "messages": [{"role": "user", "content": p}],
                    "max_tokens": max_tokens,
                    "temperature": 0,
                    "stream": True,
                },
            )
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/event-stream")
            return (await resp.read()).decode()

        return await asyncio.gather(*(one(p) for p in prompts))
    finally:
        await client.close()
        await manager.unload_model()


def _sse_ab(model_dir, prompts, reference):
    """The dense reference and the served default of one parity run,
    normalized for comparison."""
    dense = asyncio.run(_sse_burst(model_dir, prompts, reference=reference))
    paged = asyncio.run(_sse_burst(model_dir, prompts))
    return ([_normalize_sse(s) for s in dense],
            [_normalize_sse(s) for s in paged])


@pytest.mark.http
def test_ragged_legacy_sse_byte_parity(tiny_llama_dir, ragged_env):
    """Against the dense engine under the LEGACY adapter, mixed burst: SSE
    byte streams identical after normalizing id + created — chunk
    boundaries, deltas, finish reasons, usage, framing.  Variable prompt
    lengths land mid-block on purpose so the kernel's live-clamp (dead
    table entries past each slot's blocks) is on the serving path, not just
    the unit tier."""
    from dnet_tpu.api.strategies import BatchedLocalAdapter

    prompts = ["Hi", "Hello there", "A quick brown fox", "mid prompt here"]
    dense, ragged = _sse_ab(tiny_llama_dir, prompts, BatchedLocalAdapter)
    assert ragged == dense
    for s in ragged:  # real streams, not error shortcuts
        events = [ln for ln in s.splitlines() if ln.startswith("data: ")]
        assert events[-1] == "data: [DONE]" and len(events) > 2


@pytest.mark.http
def test_ragged_sched_sse_byte_parity(tiny_llama_dir, ragged_env):
    """Same contract with the scheduler on both sides: mixed prefill+decode
    ticks dispatch the in-place program and the byte streams still match
    the scheduler's run over dense slots (sched/policy.py handles an engine
    without a pool)."""
    from dnet_tpu.sched import SchedulerAdapter

    prompts = ["Hi", "Hello there", "A quick brown fox", "tail"]
    dense, ragged = _sse_ab(tiny_llama_dir, prompts, SchedulerAdapter)
    assert ragged == dense
    for s in ragged:
        events = [ln for ln in s.splitlines() if ln.startswith("data: ")]
        assert events[-1] == "data: [DONE]" and len(events) > 2


# ---------------------------------------------------------------------------
# engine tier: the sharing edges, the pool vs the dense reference
# ---------------------------------------------------------------------------


def _engine(tiny_llama_dir, paged: bool, **kw):
    from dnet_tpu.core.batch import BatchedEngine

    kw.setdefault("slots", 4)
    kw.setdefault("max_seq", 64)
    kw.setdefault("param_dtype", "float32")
    return BatchedEngine(tiny_llama_dir, kv_paged=None if paged else False, **kw)


def _stream(eng, nonce, ids, steps, dec=DecodingParams(temperature=0.0)):
    res = eng.prefill_and_sample(nonce, ids, dec)
    toks = [int(res.token[0])]
    for _ in range(steps - 1):
        out, errs = eng.decode_batch({nonce: (toks[-1], dec)})
        assert not errs
        toks.append(int(out[nonce].token[0]))
    return toks


def _span_counts():
    fam = metric("dnet_span_ms")
    return {
        sp: fam.labels(span=f"dnet.decode.{sp}").count
        for sp in ("prepare", "launch", "readback", "unpack")
    }


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_ragged_engine_flag_and_phases(tiny_llama_dir, ragged_env, paged):
    """With no argument the engine takes the pool (kv_pool is there),
    and a decode dispatch is the same four spans, once each, on the pool
    and on dense slots: the pool's append rides inside the launch.  No
    setting is needed: the spans are always on and fence nothing."""
    eng = _engine(tiny_llama_dir, paged=paged)
    try:
        assert (eng.kv_pool is not None) is paged and (eng.kv is None) is paged
        before = _span_counts()
        dec = DecodingParams(temperature=0.0)
        res = eng.prefill_and_sample("ph", [256, 72, 101], dec)
        eng.decode_batch({"ph": (int(res.token[0]), dec)})
        moved = {k: v - before[k] for k, v in _span_counts().items()}
        assert moved == {"prepare": 1, "launch": 1, "readback": 1, "unpack": 1}
        eng.end_session("ph")
    finally:
        eng.close()


def test_ragged_interleaved_mid_block_matches_dense(tiny_llama_dir, ragged_env):
    """>= 3 concurrent variable-length sessions whose positions straddle
    block boundaries (the clamped-dead-block masking edge, mid-block pos):
    identical greedy streams to the dense engine, with and without a
    budget riding along (it never widens a dispatch)."""
    prompts = {
        "va": [256, 72, 101],                                  # 1 block, mid
        "vb": [256, 84, 104, 105, 110, 3, 9, 12, 44, 7, 81],   # 2 blocks
        "vc": list(range(300, 318)),                           # 3 blocks, mid
    }
    dec = DecodingParams(temperature=0.0)

    def interleaved(eng, steps=6):
        last, got = {}, {}
        for n, ids in prompts.items():
            res = eng.prefill_and_sample(n, ids, dec)
            last[n] = int(res.token[0])
            got[n] = [last[n]]
        for _ in range(steps - 1):
            out, errs = eng.decode_batch({n: (last[n], dec) for n in prompts})
            assert not errs
            for n, res in out.items():
                last[n] = int(res.token[0])
                got[n].append(last[n])
        for n in prompts:
            eng.end_session(n)
        return got

    def chunked(eng):
        toks = _stream(eng, "ck", prompts["vb"], 1)
        while len(toks) < 12:
            out, errs = eng.decode_batch(
                {"ck": (toks[-1], dec)}, budgets={"ck": 12 - len(toks)}
            )
            assert not errs
            toks.append(int(out["ck"].token[0]))
        eng.end_session("ck")
        return toks

    eng = _engine(tiny_llama_dir, paged=False)
    try:
        want, want_ck = interleaved(eng), chunked(eng)
    finally:
        eng.close()
    eng = _engine(tiny_llama_dir, paged=True)
    try:
        assert eng.kv_pool is not None
        assert interleaved(eng) == want
        assert chunked(eng) == want_ck
        eng.kv_pool.check_conservation()
    finally:
        eng.close()


def test_ragged_cow_mid_block_divergence(tiny_llama_dir, ragged_env):
    """A prompt diverging INSIDE a shared block over the pool: the sharer
    COWs the partial block, both streams match the dense engine's (which
    shares nothing), and the original keeps decoding out of its UN-mutated
    partial block (the kernel reads the pre-COW physical block through its
    own table while the sharer's table points at the copy)."""
    from dnet_tpu.obs import reset_obs

    reset_obs()
    base = list(range(260, 280))  # 20 tokens: 2 full blocks + 4 in a 3rd
    grown = base + [7, 2]

    def run(paged: bool):
        eng = _engine(tiny_llama_dir, paged=paged, prefix_cache_size=4 * paged)
        try:
            if paged:
                eng.paged_prefix.min_tokens = 8
            got_base = [_stream(eng, "b", base, 1)[0]]
            got_grown = _stream(eng, "g", grown, 6)
            dec = DecodingParams(temperature=0.0)
            for _ in range(5):
                out, errs = eng.decode_batch({"b": (got_base[-1], dec)})
                assert not errs
                got_base.append(int(out["b"].token[0]))
            eng.end_session("b")
            eng.end_session("g")
            if paged:
                eng.kv_pool.check_conservation()
            return got_base, got_grown
        finally:
            eng.close()

    want = run(paged=False)
    cow_before = metric("dnet_kv_cow_copies_total").value
    got = run(paged=True)
    assert got == want
    assert metric("dnet_kv_cow_copies_total").value > cow_before


@pytest.mark.slow
def test_ragged_preempt_resume_reprefill_parity(tiny_llama_dir, monkeypatch):
    """Scheduler preemption -> resume over the pool: a pool too small for
    both sequences' decode growth forces a block-starvation preemption;
    the victim's prefix is aliased out, it resumes by RE-PREFILLING (the
    pool serves both the re-prefill commit and the resumed decode),
    and both final texts equal uncontended solo runs."""
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")
    # the chat-templated prompt is 45 tokens = 6 blocks: 13 admits BOTH
    # residents (12 blocks) but cannot cover their decode growth to
    # max_seq (8 blocks each), so the pool starves mid-decode
    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", "13")
    monkeypatch.setenv("DNET_SCHED_SLOTS", "2")
    reset_settings_cache()

    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager
    from dnet_tpu.api.schemas import ChatCompletionRequest

    def req(content, deadline_s=None):
        body = {
            "model": "tiny",
            "messages": [{"role": "user", "content": content}],
            "max_tokens": 28,
            "temperature": 0.0,
        }
        if deadline_s is not None:
            body["deadline_s"] = deadline_s
        return ChatCompletionRequest.model_validate(body)

    async def serve(prompts, deadlines):
        inference = InferenceManager(
            adapter=None, request_timeout_s=120.0, max_concurrent=2
        )
        manager = LocalModelManager(
            inference, max_seq=64, param_dtype="float32", batch_slots=2
        )
        await manager.load_model(str(tiny_llama_dir))
        try:
            outs = await asyncio.gather(*(
                inference.generate(req(p, deadline_s=dl))
                for p, dl in zip(prompts, deadlines)
            ))
            return [o.choices[0].message.content for o in outs]
        finally:
            await manager.unload_model()

    prompts = ["a" * 20, "b" * 20]
    try:
        solo = [asyncio.run(serve([p], [None]))[0] for p in prompts]
        before = metric("dnet_sched_preemptions_total").labels(
            reason="block_starvation"
        ).value
        # the second request carries the tight deadline -> it out-ranks the
        # first, which becomes the block-starvation victim mid-decode
        got = asyncio.run(serve(prompts, [None, 30.0]))
    finally:
        reset_settings_cache()
    assert got == solo
    after = metric("dnet_sched_preemptions_total").labels(
        reason="block_starvation"
    ).value
    assert after > before  # a preemption actually happened
