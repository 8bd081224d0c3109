"""Prefix caching: hit/miss mechanics and logits parity with cold prefill."""

import numpy as np
import pytest

from dnet_tpu.core.types import DecodingParams

pytestmark = pytest.mark.core


def test_lookup_semantics():
    import jax.numpy as jnp

    from dnet_tpu.core.prefix_cache import PrefixCache

    pc = PrefixCache(capacity=2, min_tokens=1)
    kv = {"k": jnp.zeros((2, 2))}
    pc.store([1, 2, 3], kv)
    # exact prompt: no hit (at least one token must remain to prefill)
    assert pc.lookup([1, 2, 3]) is None
    # longer prompt with the cached prefix: hit
    n, got = pc.lookup([1, 2, 3, 4])
    assert n == 3 and got["k"].shape == (2, 2)
    # diverging prompt: miss
    assert pc.lookup([1, 9, 3, 4]) is None
    # LRU eviction at capacity
    pc.store([5, 6], kv)
    pc.store([7, 8], kv)
    assert pc.lookup([1, 2, 3, 4]) is None  # evicted (oldest)
    assert pc.lookup([5, 6, 0]) is not None


def test_prefill_hit_matches_cold(tiny_llama_dir):
    from dnet_tpu.core.engine import LocalEngine

    system = [256, 83, 89, 83, 84, 69, 77]  # shared "system prompt"
    q1 = system + [72, 105]
    q2 = system + [66, 121, 101]

    cold = LocalEngine(tiny_llama_dir, max_seq=64, param_dtype="float32")
    ref1 = np.asarray(cold.prefill("a", q1), np.float32)
    cold.end_session("a")
    ref2 = np.asarray(cold.prefill("b", q2), np.float32)
    cold.end_session("b")

    warm = LocalEngine(
        tiny_llama_dir, max_seq=64, param_dtype="float32", prefix_cache_size=2
    )
    warm.prefix_cache.min_tokens = 1  # tiny test prompts
    got1 = np.asarray(warm.prefill("a", q1), np.float32)
    warm.end_session("a")
    assert warm.prefix_cache.stats == {"hits": 0, "misses": 1, "stores": 1}
    # q2 shares only `system` with the cached full q1 prompt -> miss (q1 is
    # not a prefix of q2), but after caching q2's own prompt, a q2 + suffix
    # request hits
    got2 = np.asarray(warm.prefill("b", q2), np.float32)
    warm.end_session("b")
    np.testing.assert_allclose(got1, ref1, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got2, ref2, atol=1e-5, rtol=1e-5)

    q3 = q2 + [33]
    got3 = np.asarray(warm.prefill("c", q3), np.float32)
    assert warm.prefix_cache.stats["hits"] == 1
    ref3 = np.asarray(cold.prefill("c", q3), np.float32)
    np.testing.assert_allclose(got3, ref3, atol=1e-4, rtol=1e-4)

    # decode continues correctly from a hit-restored session
    toks_warm = [
        r.token_id
        for r in warm.generate(q3, DecodingParams(temperature=0.0), max_tokens=4, nonce="d")
    ]
    toks_cold = [
        r.token_id
        for r in cold.generate(q3, DecodingParams(temperature=0.0), max_tokens=4, nonce="d")
    ]
    assert toks_warm == toks_cold


def test_snapshot_survives_donation(tiny_llama_dir):
    """The cached KV must stay valid after the borrowing session decodes
    (engine step fns donate their KV buffers)."""
    from dnet_tpu.core.engine import LocalEngine

    eng = LocalEngine(
        tiny_llama_dir, max_seq=64, param_dtype="float32", prefix_cache_size=2
    )
    eng.prefix_cache.min_tokens = 1  # tiny test prompts
    base = [256, 72, 101, 108]
    list(eng.generate(base + [108], DecodingParams(temperature=0.0), max_tokens=3, nonce="a"))
    # hit + decode (donates the restored copy)...
    out1 = [
        r.token_id
        for r in eng.generate(base + [108, 111], DecodingParams(temperature=0.0), max_tokens=3, nonce="b")
    ]
    # ...then the SAME cached entry must serve an identical second request
    out2 = [
        r.token_id
        for r in eng.generate(base + [108, 111], DecodingParams(temperature=0.0), max_tokens=3, nonce="c")
    ]
    assert out1 == out2
    assert eng.prefix_cache.stats["hits"] >= 2


def test_too_long_prompt_leaves_no_poisoned_session(tiny_llama_dir):
    """A hit-eligible but over-length prompt must fail cleanly: no session
    is left behind at a nonzero position (a retry would silently prefill at
    the stale offset)."""
    from dnet_tpu.core.engine import LocalEngine

    eng = LocalEngine(
        tiny_llama_dir, max_seq=32, param_dtype="float32", prefix_cache_size=2
    )
    eng.prefix_cache.min_tokens = 1
    base = list(range(1, 21))
    eng.prefill("a", base)
    eng.end_session("a")
    with pytest.raises(ValueError, match="exceeds max_seq"):
        eng.prefill("b", base + list(range(21, 41)))  # 40 > 32
    assert "b" not in eng.sessions
    assert eng.prefix_cache.stats["hits"] == 0  # rejected before lookup

def test_tiny_prompts_not_stored():
    import jax.numpy as jnp

    from dnet_tpu.core.prefix_cache import PrefixCache

    pc = PrefixCache(capacity=2, min_tokens=16)
    pc.store(list(range(8)), {"k": jnp.zeros((1,))})
    assert pc.stats["stores"] == 0


def test_batched_engine_prefix_cache_hits(tiny_llama_dir):
    """Chunk-aware prefix path on the batched engine: the second identical-
    prefix request seeds from the snapshot and prefills only the suffix."""
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams

    eng = BatchedEngine(
        tiny_llama_dir, slots=2, max_seq=128, param_dtype="float32",
        prefix_cache_size=2,
    )
    prompt = [256] + list(range(40, 80))  # 41 tokens (>= min_tokens)
    dec = DecodingParams(temperature=0.0)

    # request 1 via the chunk API (as BatchedLocalAdapter drives it)
    assert eng.seed_from_prefix("r1", prompt, None) == 0
    logits = eng.prefill_chunk("r1", prompt)
    eng.store_prefix("r1", prompt)
    r1 = eng.adopt_prefilled("r1", logits, dec)
    eng.end_session("r1")

    # request 2: same prompt + new turn -> suffix-only prefill
    prompt2 = prompt + [99, 98, 97]
    n = eng.seed_from_prefix("r2", prompt2, None)
    assert n == len(prompt)
    logits2 = eng.prefill_chunk("r2", prompt2[n:])
    r2 = eng.adopt_prefilled("r2", logits2, dec)
    # the capacity went to whichever layout serves: block aliasing over the
    # pool (what this model derives), or the inner engine's snapshots
    assert eng.eng.prefix_cache is None
    assert eng.paged_prefix.stats["hits"] == 1

    # equivalence: suffix-only prefill == full prefill
    full = eng.prefill_and_sample("r3", prompt2, dec)
    assert int(r2.token[0]) == int(full.token[0])


def test_mesh_engine_prefix_cache(tiny_llama_dir, eight_devices):
    """Mesh-sharded KV snapshots: suffix-only prefill matches full prefill."""
    import numpy as np

    from dnet_tpu.parallel.engine import MeshEngine

    eng = MeshEngine(
        tiny_llama_dir, pp=2, tp=2, max_seq=128, param_dtype="float32",
        prefix_cache_size=2,
    )
    prompt = [256] + list(range(40, 80))
    eng.prefill("a", prompt)
    eng.end_session("a")
    assert eng.prefix_cache.stats["stores"] == 1

    prompt2 = prompt + [99, 98]
    hit_logits = np.asarray(eng.prefill("b", prompt2), np.float32)
    assert eng.prefix_cache.stats["hits"] == 1
    eng.end_session("b")
    eng.prefix_cache.clear()
    full_logits = np.asarray(eng.prefill("c", prompt2), np.float32)
    np.testing.assert_allclose(hit_logits, full_logits, atol=1e-4, rtol=1e-4)


def test_chunked_prefill_interleaves_with_decode(tiny_llama_dir):
    """While a long prompt prefills chunk-by-chunk, an active lane's decode
    steps run BETWEEN chunks — the stall is bounded by one chunk."""
    import asyncio

    from dnet_tpu.api.strategies import BatchedLocalAdapter
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.core.types import DecodingParams

    eng = BatchedEngine(tiny_llama_dir, slots=2, max_seq=1024, param_dtype="float32")
    events = []
    orig_chunk = eng.prefill_chunk
    orig_decode = eng.decode_batch

    def chunk_spy(nonce, ids, seed=None):
        events.append("chunk")
        return orig_chunk(nonce, ids, seed)

    def decode_spy(reqs, budgets=None):
        events.append("decode")
        return orig_decode(reqs)

    eng.prefill_chunk = chunk_spy
    eng.decode_batch = decode_spy

    async def go():
        adapter = BatchedLocalAdapter(eng)
        adapter.PREFILL_CHUNK = 64
        await adapter.start()
        dec = DecodingParams(temperature=0.0)
        # active lane
        await adapter.send_tokens("fast", [256, 72], dec, 0)
        r = await adapter.await_token("fast", 0, 60.0)
        assert not r.error
        tok = r.token_id

        # long prompt starts prefilling (6 chunks of 64)
        long_ids = [256] + list(range(1, 380))
        await adapter.send_tokens("slow", long_ids, dec, 0)
        # drive the fast lane while the prefill is in flight
        for step in range(1, 6):
            await adapter.send_tokens("fast", [tok], dec, step)
            r = await adapter.await_token("fast", step, 60.0)
            assert not r.error
            tok = r.token_id
        r = await adapter.await_token("slow", 0, 60.0)
        assert not r.error
        await adapter.shutdown()

    asyncio.run(go())
    first_chunk = events.index("chunk")
    last_chunk = len(events) - 1 - events[::-1].index("chunk")
    between = events[first_chunk:last_chunk]
    assert "decode" in between, f"no decode interleaved: {events}"


def test_pipelined_engine_prefix_cache(tiny_llama_dir, eight_devices):
    """Slot-row snapshot/restore: a second request extending a cached prompt
    prefills only the suffix and produces the identical stream."""
    from dnet_tpu.core.types import DecodingParams
    from dnet_tpu.parallel.pipelined import PipelinedMeshEngine

    eng = PipelinedMeshEngine(
        tiny_llama_dir, pp=2, tp=1, slots=2, max_seq=64, param_dtype="float32",
        prefix_cache_size=4,
    )
    dec = DecodingParams(temperature=0.0)
    base = [256] + list(range(60, 76))  # >= min_tokens so the snapshot lands
    ext = base + [101, 102]
    cold = [r.token_id for r in eng.generate(ext, dec, max_tokens=6, nonce="c")]
    # prime the cache with the base prompt, then extend it: the warm request
    # must restore base's slot rows and prefill only the 2-token suffix
    list(eng.generate(base, dec, max_tokens=1, nonce="p"))
    assert eng.prefix_cache.stats["stores"] >= 1
    warm = [r.token_id for r in eng.generate(ext, dec, max_tokens=6, nonce="w")]
    assert eng.prefix_cache.stats["hits"] >= 1
    assert warm == cold
