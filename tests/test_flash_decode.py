"""Flash-decode kernel (split-K Pallas, interpret mode) vs dense attend.

Every variant must equal `ops.attention.attend` with the matching mask:
GQA, MLA asymmetric V, gpt_oss sinks, the rotating SWA ring buffer, and
the sp partial-LSE compose (vs sp_decode_attend inside shard_map).
"""

import numpy as np
import pytest

pytestmark = [pytest.mark.core, pytest.mark.parallel]


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")


def _mk(rng, B, S, H, KVH, Hd, Vd=None):
    import jax.numpy as jnp

    Vd = Hd if Vd is None else Vd
    q = jnp.asarray(rng.normal(size=(B, 1, H, Hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, Hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, Vd)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("pos", [0, 5, 31, 63])
@pytest.mark.parametrize("H,KVH", [(4, 2), (4, 4), (8, 2)])
def test_linear_matches_dense(rng, pos, H, KVH):
    import jax.numpy as jnp

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import flash_decode_attend, flash_decode_eligible

    q, k, v = _mk(rng, 2, 64, H, KVH, 16)
    assert flash_decode_eligible(q, k)
    want = attend(q, k, v, mask=causal_mask(1, 64, pos))
    got = flash_decode_attend(q, k, v, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_mla_asymmetric_v(rng):
    """V head dim != K head dim (deepseek MLA) with a custom scale."""
    import jax.numpy as jnp

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import flash_decode_attend

    q, k, v = _mk(rng, 1, 32, 4, 2, 16, Vd=24)
    want = attend(q, k, v, mask=causal_mask(1, 32, 9), scale=0.31)
    got = flash_decode_attend(q, k, v, jnp.int32(9), scale=0.31)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_sinks_match_dense(rng):
    """gpt_oss per-head sink logits fold into the denominator exactly once."""
    import jax.numpy as jnp

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import flash_decode_attend

    q, k, v = _mk(rng, 1, 32, 4, 2, 16)
    sinks = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
    want = attend(q, k, v, mask=causal_mask(1, 32, 17), sinks=sinks)
    got = flash_decode_attend(q, k, v, jnp.int32(17), sinks=sinks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pos", [3, 15, 40, 100])
def test_rotating_swa_matches_dense(rng, pos):
    """Ring-buffer cache (W slots, slot = pos % W), sliding window mask.
    Dense reference: reconstruct per-slot absolute positions and attend."""
    import jax.numpy as jnp

    from dnet_tpu.ops.attention import attend
    from dnet_tpu.ops.flash_decode import flash_decode_attend

    W, window = 16, 12
    q, k, v = _mk(rng, 2, W, 4, 2, 16)
    s = np.arange(W)[None, :]
    a = pos - np.mod(pos - s, W)
    mask = jnp.asarray((a >= 0) & (a > pos - window))  # [1, W]
    want = attend(q, k, v, mask=mask)
    got = flash_decode_attend(
        q, k, v, jnp.int32(pos), window=window, rotating=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_engine_stream_with_decode_kernel(tiny_llama_dir):
    """Full serving hot loop with the decode kernel live (interpret): the
    greedy stream must equal the dense-path stream token for token."""
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.core.types import DecodingParams

    ids = [256, 72, 101, 108, 108, 111]
    eng = LocalEngine(tiny_llama_dir, max_seq=64, param_dtype="float32")
    got = [
        r.token_id
        for r in eng.generate(ids, DecodingParams(temperature=0.0), max_tokens=6)
    ]
    eng.close()
    import os

    ref_env = os.environ.pop("DNET_FLASH_INTERPRET")
    try:
        eng = LocalEngine(tiny_llama_dir, max_seq=64, param_dtype="float32")
        want = [
            r.token_id
            for r in eng.generate(ids, DecodingParams(temperature=0.0), max_tokens=6)
        ]
        eng.close()
    finally:
        os.environ["DNET_FLASH_INTERPRET"] = ref_env
    assert got == want


def test_gpt_oss_swa_stream_with_decode_kernel(tmp_path):
    """gpt_oss mixed full/SWA layers: the rotating ring-buffer decode runs
    through the kernel variant (sinks + sliding window), stream unchanged."""
    from tests.fakes.checkpoints import make_tiny_gpt_oss

    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.core.types import DecodingParams

    d = tmp_path / "oss"
    make_tiny_gpt_oss(d)
    ids = [1, 7, 3, 11]
    import os

    eng = LocalEngine(d, max_seq=64, param_dtype="float32")
    got = [
        r.token_id
        for r in eng.generate(ids, DecodingParams(temperature=0.0), max_tokens=6)
    ]
    eng.close()
    ref_env = os.environ.pop("DNET_FLASH_INTERPRET")
    try:
        eng = LocalEngine(d, max_seq=64, param_dtype="float32")
        want = [
            r.token_id
            for r in eng.generate(ids, DecodingParams(temperature=0.0), max_tokens=6)
        ]
        eng.close()
    finally:
        os.environ["DNET_FLASH_INTERPRET"] = ref_env
    assert got == want


@pytest.mark.parametrize("pos", [10, 45, 63])
def test_sp_partials_merge_matches_dense(rng, pos):
    """The sp composition's algebra, rank by rank: run the with_lse kernel
    on each half of the KV sequence (offset = rank * S_local) and merge the
    unnormalized partials with the same log-sum-exp combine
    sp_flash_decode_attend performs with pmax/psum.  (The collective form
    executes on the CPU mesh too — tests/test_flash_mesh.py — via the
    tile-fold emulation; this test pins the KERNEL's with_lse partials.)"""
    import jax.numpy as jnp

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import NEG_INF, _decode_pallas

    B, S, H, KVH, Hd = 1, 64, 4, 2, 16
    G = H // KVH
    q, k, v = _mk(rng, B, S, H, KVH, Hd)
    S_local = S // 2
    parts = []
    for r in range(2):
        kr = k[:, r * S_local : (r + 1) * S_local]
        vr = v[:, r * S_local : (r + 1) * S_local]
        scal = jnp.asarray([pos, r * S_local], jnp.int32)
        sink0 = jnp.full((KVH, G), NEG_INF, jnp.float32)
        parts.append(
            _decode_pallas(
                q, kr, vr, scal, sink0, G=G, scale=Hd**-0.5, bk=16,
                window=0, rotating=False, with_lse=True, interpret=True,
            )
        )
    (o0, m0, l0), (o1, m1, l1) = parts
    m_glob = jnp.maximum(m0, m1)
    c0, c1 = jnp.exp(m0 - m_glob), jnp.exp(m1 - m_glob)
    l_glob = l0 * c0 + l1 * c1
    o_glob = o0 * c0.reshape(B, 1, H, 1) + o1 * c1.reshape(B, 1, H, 1)
    got = o_glob / jnp.maximum(l_glob.reshape(B, 1, H, 1), 1e-30)
    want = attend(q, k, v, mask=causal_mask(1, S, pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_packed_int4_cache_stays_off_the_kernel(rng):
    """Packed-int4 tiles (uint8 nibbles interleaved along the lane dim) have
    no Mosaic lowering: eligibility refuses them IN CODE, so cached_attend
    dequantizes through read_kv and the kernel streams f32 tiles."""
    import jax.numpy as jnp

    from dnet_tpu.core.kvcache import KVConfig, init_cache, read_kv
    from dnet_tpu.ops.attention import attend, cached_attend, causal_mask
    from dnet_tpu.ops.flash_decode import flash_decode_eligible

    B, S, H, KVH, Hd = 1, 32, 4, 2, 16
    cfg = KVConfig(
        n_layers=1, batch=B, max_seq=S, n_kv_heads=KVH, head_dim=Hd,
        quant_bits=4,
    )
    kvs = {k: v[0] for k, v in init_cache(cfg).items()}
    q = jnp.asarray(rng.normal(size=(B, 1, H, Hd)), jnp.float32)
    assert not flash_decode_eligible(q, kvs["k"])
    for t in range(10):
        k_new = jnp.asarray(rng.normal(size=(B, 1, KVH, Hd)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(B, 1, KVH, Hd)), jnp.float32)
        got, kvs = cached_attend(
            q, k_new, v_new, kvs, jnp.int32(t), None, causal=True
        )
    kc, vc = read_kv(kvs)
    want = attend(q, kc, vc, mask=causal_mask(1, S, 9))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bits", [8])
def test_quantized_cache_matches_dense(rng, bits):
    """Fused in-kernel dequant (int8 + per-slot scales) == dense attend
    over the read_kv-dequantized cache."""
    import jax.numpy as jnp

    from dnet_tpu.core.kvcache import KVConfig, init_cache, read_kv, write_kv
    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import flash_decode_attend

    B, S, H, KVH, Hd = 1, 32, 4, 2, 16
    cfg = KVConfig(
        n_layers=1, batch=B, max_seq=S, n_kv_heads=KVH, head_dim=Hd,
        quant_bits=bits,
    )
    kvs = {k: v[0] for k, v in init_cache(cfg).items()}  # strip layer axis
    pos = 0
    for t in range(10):  # token-by-token writes, like real decode
        k_new = jnp.asarray(rng.normal(size=(B, 1, KVH, Hd)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(B, 1, KVH, Hd)), jnp.float32)
        kvs = write_kv(kvs, k_new, v_new, jnp.int32(t))
        pos = t
    q = jnp.asarray(rng.normal(size=(B, 1, H, Hd)), jnp.float32)
    kc, vc = read_kv(kvs)
    want = attend(q, kc, vc, mask=causal_mask(1, S, pos))
    got = flash_decode_attend(
        q, kvs["k"], kvs["v"], jnp.int32(pos),
        k_scale=kvs["k_scale"], v_scale=kvs["v_scale"],
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bits", [8])
def test_rotating_quantized_matches_dense(rng, bits):
    """Quantized SWA ring buffer (the gpt_oss sliding layer's layout):
    per-slot scale rotation + in-kernel dequant + in-kernel ring-position
    reconstruction, all composed, vs the dense rotating reference."""
    import jax.numpy as jnp

    from dnet_tpu.core.kvcache import KVConfig, init_cache, read_kv, write_kv_rotating
    from dnet_tpu.ops.attention import attend
    from dnet_tpu.ops.flash_decode import flash_decode_attend

    B, W, window, H, KVH, Hd = 1, 16, 12, 4, 2, 16
    cfg = KVConfig(
        n_layers=1, batch=B, max_seq=64, n_kv_heads=KVH, head_dim=Hd,
        sliding_window=W, quant_bits=bits,
    )
    kvs = {k: v[0] for k, v in init_cache(cfg).items()}
    pos = 0
    for t in range(25):  # wraps the ring (25 > W): scales rotate too
        k_new = jnp.asarray(rng.normal(size=(B, 1, KVH, Hd)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(B, 1, KVH, Hd)), jnp.float32)
        kvs = write_kv_rotating(kvs, k_new, v_new, jnp.int32(t))
        pos = t
    q = jnp.asarray(rng.normal(size=(B, 1, H, Hd)), jnp.float32)
    kc, vc = read_kv(kvs)
    s = np.arange(W)[None, :]
    a = pos - np.mod(pos - s, W)
    mask = jnp.asarray((a >= 0) & (a > pos - window))
    want = attend(q, kc, vc, mask=mask)
    got = flash_decode_attend(
        q, kvs["k"], kvs["v"], jnp.int32(pos), window=window, rotating=True,
        k_scale=kvs["k_scale"], v_scale=kvs["v_scale"],
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_engine_stream_quantized_kv(tiny_llama_dir, bits):
    """Serving hot loop with a quantized cache + the fused-dequant kernel:
    greedy stream equals the dense quantized path token for token."""
    import os

    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.core.types import DecodingParams

    ids = [256, 72, 101, 108]
    eng = LocalEngine(
        tiny_llama_dir, max_seq=64, param_dtype="float32", kv_quant_bits=bits
    )
    got = [
        r.token_id
        for r in eng.generate(ids, DecodingParams(temperature=0.0), max_tokens=6)
    ]
    eng.close()
    ref_env = os.environ.pop("DNET_FLASH_INTERPRET")
    try:
        eng = LocalEngine(
            tiny_llama_dir, max_seq=64, param_dtype="float32", kv_quant_bits=bits
        )
        want = [
            r.token_id
            for r in eng.generate(ids, DecodingParams(temperature=0.0), max_tokens=6)
        ]
        eng.close()
    finally:
        os.environ["DNET_FLASH_INTERPRET"] = ref_env
    assert got == want


def test_mesh_shard_engine_stream_with_flash_live(tiny_llama_dir, eight_devices):
    """Inside shard_map (mesh-backed shard engine) the flash seams now run
    (r5): the tile-fold emulation under interpret mode, the real kernel
    with declared output vma on TPU.  The engine stream with interpret
    forced on must match the plain single-device stream token for token."""
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.core.types import DecodingParams
    from dnet_tpu.parallel.shard_mesh import MeshShardEngine

    ids = [256, 72, 101, 108]
    dec = DecodingParams(temperature=0.0)
    local = LocalEngine(tiny_llama_dir, max_seq=64, param_dtype="float32")
    want = [r.token_id for r in local.generate(ids, dec, max_tokens=5)]
    local.close()
    eng = MeshShardEngine(
        tiny_llama_dir, layers=range(4), tp=2, devices=eight_devices[:2],
        max_seq=64, param_dtype="float32",
    )
    got = [r.token_id for r in eng.generate(ids, dec, max_tokens=5)]
    eng.close()
    assert got == want
