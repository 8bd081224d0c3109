"""Flash kernels INSIDE shard_map (VERDICT r4 next #1).

Two levels of evidence, neither needing TPU hardware:

1. Executed equivalence: under DNET_FLASH_INTERPRET=1 the mesh paths run
   the jnp tile-fold emulation (same math, same fold order as the kernel)
   THROUGH the real shard_map programs — tp-sharded decode/prefill and the
   sp composition's LSE combine with real pmax/psum collectives — and must
   match the dense reference.
2. Trace legality of the REAL kernel: jax.make_jaxpr of a shard_map body
   invoking the non-interpret pallas_call with declared output vma — jax's
   check_vma runs at trace time, so a wrong declaration fails HERE, not on
   the first TPU run.
"""

import numpy as np
import pytest

from jax import shard_map

pytestmark = [pytest.mark.core, pytest.mark.parallel]


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("DNET_FLASH_INTERPRET", "1")


def _mk(rng, B, S, H, KVH, Hd):
    import jax.numpy as jnp

    q = jnp.asarray(rng.normal(size=(B, 1, H, Hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, Hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, Hd)), jnp.float32)
    return q, k, v


def _tp_mesh(eight_devices, n=2):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(eight_devices[:n]), ("tp",))


@pytest.mark.parametrize("pos", [5, 40, 63])
def test_tp_sharded_flash_decode_matches_dense(rng, eight_devices, pos):
    """Head-sharded (tp2) flash decode inside shard_map == dense attend."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import flash_decode_attend, flash_decode_eligible

    B, S, H, KVH, Hd = 2, 64, 8, 4, 16
    q, k, v = _mk(rng, B, S, H, KVH, Hd)
    mesh = _tp_mesh(eight_devices)

    def body(q, k, v):
        assert flash_decode_eligible(q, k), "kernel must be eligible in-mesh"
        return flash_decode_attend(q, k, v, jnp.int32(pos))

    got = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, "tp"), P(None, None, "tp"), P(None, None, "tp")),
        out_specs=P(None, None, "tp"),
    )(q, k, v)
    want = attend(q, k, v, mask=causal_mask(1, S, pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_tp_sharded_rotating_swa_matches_dense(rng, eight_devices):
    """The gpt_oss rotating ring-buffer variant, head-sharded in-mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dnet_tpu.ops.attention import attend
    from dnet_tpu.ops.flash_decode import flash_decode_attend

    W, window, pos = 16, 12, 40
    q, k, v = _mk(rng, 1, W, 8, 4, 16)
    mesh = _tp_mesh(eight_devices)

    def body(q, k, v):
        return flash_decode_attend(
            q, k, v, jnp.int32(pos), window=window, rotating=True
        )

    got = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, "tp"), P(None, None, "tp"), P(None, None, "tp")),
        out_specs=P(None, None, "tp"),
    )(q, k, v)
    s = np.arange(W)[None, :]
    a = pos - np.mod(pos - s, W)
    mask = jnp.asarray((a >= 0) & (a > pos - window))
    want = attend(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pos", [10, 45, 63])
def test_sp_flash_compose_executes_in_shard_map(rng, eight_devices, pos):
    """THE 128K money path (BASELINE config 5's per-token bound), finally
    executed: sp_flash_decode_attend inside a real sp2 shard_map — emulated
    per-rank partials + the REAL pmax/psum LSE combine — == dense attend
    over the full sequence."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import sp_flash_decode_attend, sp_flash_eligible

    B, S, H, KVH, Hd = 1, 64, 4, 2, 16
    q, k, v = _mk(rng, B, S, H, KVH, Hd)
    mesh = _tp_mesh(eight_devices)  # one axis named tp; used as the sp axis

    def body(q, k, v):
        assert sp_flash_eligible(q, k), "sp composition must be eligible"
        return sp_flash_decode_attend(q, k, v, jnp.int32(pos), "tp")

    got = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "tp"), P(None, "tp")),
        out_specs=P(),
    )(q, k, v)
    want = attend(q, k, v, mask=causal_mask(1, S, pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_sp_flash_with_sinks_matches_dense(rng, eight_devices):
    """Sink logits fold exactly once at the GLOBAL combine level."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import sp_flash_decode_attend

    B, S, H, KVH, Hd = 1, 64, 4, 2, 16
    q, k, v = _mk(rng, B, S, H, KVH, Hd)
    sinks = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    mesh = _tp_mesh(eight_devices)

    def body(q, k, v):
        return sp_flash_decode_attend(q, k, v, jnp.int32(45), "tp", sinks=sinks)

    got = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "tp"), P(None, "tp")),
        out_specs=P(),
    )(q, k, v)
    want = attend(q, k, v, mask=causal_mask(1, S, 45), sinks=sinks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_sp_rank_entirely_past_pos(rng, eight_devices):
    """A rank whose KV shard lies wholly beyond pos must contribute zero
    weight (m=NEG_INF, l=0 partials) — the dead-tile gating the emulation
    shares with the kernel."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_decode import sp_flash_decode_attend

    B, S, H, KVH, Hd = 1, 64, 4, 2, 16
    pos = 20  # < S/2: rank 1's shard [32, 64) is entirely dead
    q, k, v = _mk(rng, B, S, H, KVH, Hd)
    mesh = _tp_mesh(eight_devices)

    def body(q, k, v):
        return sp_flash_decode_attend(q, k, v, jnp.int32(pos), "tp")

    got = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "tp"), P(None, "tp")),
        out_specs=P(),
    )(q, k, v)
    want = attend(q, k, v, mask=causal_mask(1, S, pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_tp_sharded_flash_prefill_matches_dense(rng, eight_devices):
    """Head-sharded causal PREFILL flash inside shard_map == dense."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dnet_tpu.ops.attention import attend, causal_mask
    from dnet_tpu.ops.flash_attention import flash_attend_causal, flash_eligible

    B, T, S, H, KVH, Hd = 1, 16, 64, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(B, T, H, Hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, Hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, Hd)), jnp.float32)
    pos = 4
    mesh = _tp_mesh(eight_devices)

    def body(q, k, v):
        assert flash_eligible(q, k, v), "prefill kernel must be eligible in-mesh"
        return flash_attend_causal(q, k, v, pos)

    got = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, "tp"), P(None, None, "tp"), P(None, None, "tp")),
        out_specs=P(None, None, "tp"),
    )(q, k, v)
    want = attend(q, k, v, mask=causal_mask(T, S, pos))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_real_kernel_vma_trace_legal(rng, eight_devices, monkeypatch):
    """The NON-interpret pallas paths with declared vma must pass jax's
    check_vma at trace time: make_jaxpr of shard_map bodies invoking the
    real kernels (prefetch-grid decode with invariant scalars, SMEM sp
    decode with varying scalars, prefill) — a wrong vma declaration fails
    here, not on the first real-TPU serve."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dnet_tpu.ops.flash_attention import _flash_pallas
    from dnet_tpu.ops.flash_decode import _decode_pallas

    monkeypatch.delenv("DNET_FLASH_INTERPRET", raising=False)
    B, S, H, KVH, Hd = 1, 64, 8, 4, 16
    G = H // KVH
    q, k, v = _mk(rng, B, S, H, KVH, Hd)
    mesh = _tp_mesh(eight_devices)

    def tp_decode(q, k, v):
        scal = jnp.asarray([40, 0], jnp.int32)
        sink = jnp.full((KVH // 2, G), -1e30, jnp.float32)
        return _decode_pallas(
            q, k, v, scal, sink, G=G, scale=0.25, bk=16, window=0,
            rotating=False, with_lse=False, interpret=False, vma=("tp",),
        )

    jax.make_jaxpr(
        shard_map(
            tp_decode, mesh=mesh,
            in_specs=(P(None, None, "tp"), P(None, None, "tp"), P(None, None, "tp")),
            out_specs=P(None, None, "tp"),
        )
    )(q, k, v)

    def sp_decode(q, k, v):
        offset = jax.lax.axis_index("tp") * (S // 2)
        scal = jnp.stack([jnp.int32(40), offset.astype(jnp.int32)])
        sink = jnp.full((KVH, G), -1e30, jnp.float32)
        o, m, l = _decode_pallas(
            q, k, v, scal, sink, G=G, scale=0.25, bk=16, window=0,
            rotating=False, with_lse=True, interpret=False, vma=("tp",),
            scal_varying=True,
        )
        # partials are tp-varying by declaration; reduce before returning
        return tuple(jax.lax.psum(x, "tp") for x in (o, m, l))

    jax.make_jaxpr(
        shard_map(
            sp_decode, mesh=mesh,
            in_specs=(P(), P(None, "tp"), P(None, "tp")),
            out_specs=(P(), P(), P()),
        )
    )(q, k, v)

    T = 16
    qp = jnp.asarray(rng.normal(size=(B, T, H, Hd)), jnp.float32)

    def tp_prefill(q, k, v):
        sink = jnp.full((H // 2,), -1e30, jnp.float32)
        return _flash_pallas(
            q, k, v, jnp.asarray([0], jnp.int32), sink, G=G, scale=0.25,
            bq=8, bk=16, interpret=False, vma=("tp",),
        )

    jax.make_jaxpr(
        shard_map(
            tp_prefill, mesh=mesh,
            in_specs=(P(None, None, "tp"), P(None, None, "tp"), P(None, None, "tp")),
            out_specs=P(None, None, "tp"),
        )
    )(qp, k, v)
