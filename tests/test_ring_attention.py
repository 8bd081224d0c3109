"""Ring attention / sp decode attention vs dense reference (8 CPU devices)."""

import jax

from jax import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dnet_tpu.ops.attention import attend, causal_mask
from dnet_tpu.ops.ring_attention import ring_attend, sp_decode_attend

pytestmark = pytest.mark.parallel


def make_qkv(rng, B=1, S=32, H=4, KVH=2, Hd=16):
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, Hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, S, KVH, Hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, S, KVH, Hd)).astype(np.float32))
    return q, k, v


@pytest.fixture(scope="module")
def sp_mesh(eight_devices):
    import numpy as np_

    return Mesh(np_.array(eight_devices[:4]).reshape(4), ("sp",))


def test_ring_attend_matches_dense_causal(sp_mesh, rng):
    SP, S = 4, 32
    q, k, v = make_qkv(rng, S=S)
    dense = attend(q, k, v, mask=causal_mask(S, S, 0))

    positions = jnp.arange(S)

    def spmd(q_blk, k_blk, v_blk, qpos, kvpos):
        return ring_attend(q_blk, k_blk, v_blk, qpos, kvpos, "sp")

    fn = shard_map(
        spmd,
        mesh=sp_mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P("sp"), P("sp")),
        out_specs=P(None, "sp"),
    )
    out = fn(q, k, v, positions, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5, rtol=2e-5)


def test_ring_attend_non_causal(sp_mesh, rng):
    S = 32
    q, k, v = make_qkv(rng, S=S)
    dense = attend(q, k, v, mask=None)
    positions = jnp.arange(S)

    fn = shard_map(
        lambda qb, kb, vb, qp, kp: ring_attend(qb, kb, vb, qp, kp, "sp", causal=False),
        mesh=sp_mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P("sp"), P("sp")),
        out_specs=P(None, "sp"),
    )
    out = fn(q, k, v, positions, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5, rtol=2e-5)


def test_sp_decode_matches_dense(sp_mesh, rng):
    """Single-query decode against an S-long cache sharded over 4 ranks."""
    S, H, KVH, Hd = 32, 4, 2, 16
    q = jnp.asarray(rng.normal(0, 1, (1, 1, H, Hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, S, KVH, Hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, S, KVH, Hd)).astype(np.float32))
    # decode at absolute position 24: only slots < 25 are valid
    pos = 24
    dense_mask = (jnp.arange(S) <= pos)[None, :]
    dense = attend(q, k, v, mask=dense_mask)

    positions = jnp.arange(S)

    def spmd(kb, vb, kvpos):
        valid = (kvpos <= pos)[None, :]  # [1, S_local]
        return sp_decode_attend(q, kb, vb, valid, "sp")

    fn = shard_map(
        spmd,
        mesh=sp_mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P("sp")),
        out_specs=P(),
    )
    out = fn(k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5, rtol=2e-5)


def test_sp_decode_custom_scale_matches_dense(sp_mesh, rng):
    """A non-default softmax scale (MLA YaRN mscale^2 compensation) must
    survive the sp combine — sp_decode_attend used to hardcode Hd**-0.5."""
    S, H, KVH, Hd = 32, 4, 2, 16
    scale = 2.5 * Hd**-0.5  # what yarn mscale^2 does to MLA's base scale
    q = jnp.asarray(rng.normal(0, 1, (1, 1, H, Hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, S, KVH, Hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, S, KVH, Hd)).astype(np.float32))
    pos = 24
    dense = attend(q, k, v, mask=(jnp.arange(S) <= pos)[None, :], scale=scale)
    positions = jnp.arange(S)

    def spmd(kb, vb, kvpos):
        valid = (kvpos <= pos)[None, :]
        return sp_decode_attend(q, kb, vb, valid, "sp", scale=scale)

    fn = shard_map(
        spmd,
        mesh=sp_mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P("sp")),
        out_specs=P(),
    )
    out = fn(k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5, rtol=2e-5)


def test_ring_attend_gqa_grouping(sp_mesh, rng):
    """H=8 over KVH=2 (G=4) grouping must match dense GQA."""
    S = 16
    q, k, v = make_qkv(rng, S=S, H=8, KVH=2, Hd=8)
    dense = attend(q, k, v, mask=causal_mask(S, S, 0))
    positions = jnp.arange(S)
    fn = shard_map(
        lambda qb, kb, vb, qp, kp: ring_attend(qb, kb, vb, qp, kp, "sp"),
        mesh=sp_mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P("sp"), P("sp")),
        out_specs=P(None, "sp"),
    )
    out = fn(q, k, v, positions, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5, rtol=2e-5)
