"""MoE dispatch ops: capacity routing, expert-sharded dispatch, a2a EP.

The dense masked-einsum path is the numerical reference (it is exact by
construction); dispatch/a2a must match it whenever capacity is exact
(no drops).  The reference framework computes MoE densely and has no
expert parallelism (SURVEY.md §2.8), so these tests pin down the
beyond-reference semantics.
"""

import jax

from jax import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dnet_tpu.ops.moe import (
    expert_capacity,
    gather_from_experts,
    localize_topk,
    moe_a2a,
    moe_dispatch,
    moe_dispatch_sharded,
    resolve_moe_impl,
    route_positions,
    scatter_to_experts,
)

pytestmark = pytest.mark.core


def _dense_ref(flat, top_idx, top_w, wlist):
    """Reference: per-token loop over its top-k experts."""
    out = np.zeros_like(np.asarray(flat, dtype=np.float32))
    for t in range(flat.shape[0]):
        for s in range(top_idx.shape[1]):
            e = int(top_idx[t, s])
            out[t] += float(top_w[t, s]) * np.asarray(
                wlist(e, np.asarray(flat[t], dtype=np.float32))
            )
    return out


def test_expert_capacity():
    assert expert_capacity(64, 8, 2, 1.0) == 16
    assert expert_capacity(64, 8, 2, 1.25) == 20
    assert expert_capacity(64, 8, 2, 0.0) == 64  # exact: no drops possible
    assert expert_capacity(4, 8, 2, 1.0) == 1  # floor
    assert expert_capacity(100, 4, 1, 100.0) == 100  # capped at n


def test_route_positions_hand_checked():
    idx = jnp.array([[0, 1], [0, 2], [1, 0], [2, 2]], dtype=jnp.int32)
    pos = np.asarray(route_positions(idx, 3))
    # expert 0 receives slots in order (t0,s0),(t1,s0),(t2,s1) -> 0,1,2
    assert pos[0, 0] == 0 and pos[1, 0] == 1 and pos[2, 1] == 2
    # expert 1: (t0,s1),(t2,s0) -> 0,1 ; expert 2: (t1,s1),(t3,s0),(t3,s1)
    assert pos[0, 1] == 0 and pos[2, 0] == 1
    assert pos[1, 1] == 0 and pos[3, 0] == 1 and pos[3, 1] == 2


def test_localize_topk_sentinel():
    idx = jnp.array([[0, 5], [2, 3]], dtype=jnp.int32)
    loc = np.asarray(localize_topk(idx, 2, 2))  # local range [2, 4)
    assert loc.tolist() == [[2, 2], [0, 1]]  # non-local -> sentinel n_local=2


def test_scatter_gather_roundtrip(rng):
    N, k, E, C, D = 16, 2, 4, 16, 8
    flat = jnp.asarray(rng.normal(size=(N, D)), dtype=jnp.float32)
    logits = jnp.asarray(rng.normal(size=(N, E)), dtype=jnp.float32)
    _, top_idx = lax.top_k(logits, k)
    top_w = jnp.ones((N, k), dtype=jnp.float32)
    pos = route_positions(top_idx, E)
    xe = scatter_to_experts(flat, top_idx, pos, E, C)
    # identity ffn: gather must reproduce sum over k of the token itself
    out = gather_from_experts(xe, top_idx, pos, top_w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(flat) * k, rtol=1e-6)


def test_moe_dispatch_matches_dense(rng):
    N, k, E, D, F = 32, 2, 8, 16, 12
    flat = jnp.asarray(rng.normal(size=(N, D)), dtype=jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, dtype=jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(E, F, D)) * 0.1, dtype=jnp.float32)
    logits = jnp.asarray(rng.normal(size=(N, E)), dtype=jnp.float32)
    top_w, top_idx = lax.top_k(jax.nn.softmax(logits), k)

    def ffn(xe):
        return jnp.einsum("ecf,efd->ecd", jax.nn.relu(jnp.einsum("ecd,edf->ecf", xe, w1)), w2)

    got = moe_dispatch(flat, top_idx, top_w, ffn, E, expert_capacity(N, E, k, 0.0))
    ref = _dense_ref(
        flat, np.asarray(top_idx), np.asarray(top_w),
        lambda e, x: np.maximum(x @ np.asarray(w1[e]), 0.0) @ np.asarray(w2[e]),
    )
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=2e-5)


def test_moe_dispatch_capacity_drops(rng):
    """With capacity 1, each expert serves exactly its first-arriving slot;
    later slots contribute zero — outputs stay finite and bounded."""
    N, k, E, D = 8, 2, 2, 4
    flat = jnp.ones((N, D), dtype=jnp.float32)
    top_idx = jnp.zeros((N, k), dtype=jnp.int32).at[:, 1].set(1)  # all -> experts 0,1
    top_w = jnp.ones((N, k), dtype=jnp.float32)
    got = moe_dispatch(flat, top_idx, top_w, lambda xe: xe, E, 1)
    arr = np.asarray(got)
    # token 0 kept in both experts; all later tokens dropped entirely
    np.testing.assert_allclose(arr[0], 2.0 * np.ones(D))
    np.testing.assert_allclose(arr[1:], 0.0)


@pytest.mark.parametrize("impl", ["sharded", "a2a"])
def test_moe_sharded_matches_dense(rng, eight_devices, impl):
    """4-rank expert parallelism == single-rank dense, exact capacity."""
    Rk = 4
    N, k, E, D, F = 32, 2, 8, 16, 12
    mesh = Mesh(np.array(eight_devices[:Rk]), ("ep",))
    flat = jnp.asarray(rng.normal(size=(N, D)), dtype=jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, dtype=jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(E, F, D)) * 0.1, dtype=jnp.float32)
    logits = jnp.asarray(rng.normal(size=(N, E)), dtype=jnp.float32)
    top_w, top_idx = lax.top_k(jax.nn.softmax(logits), k)

    def local_ffn(w1_l, w2_l):
        def ffn(xe):
            return jnp.einsum(
                "ecf,efd->ecd", jax.nn.relu(jnp.einsum("ecd,edf->ecf", xe, w1_l)), w2_l
            )
        return ffn

    if impl == "sharded":
        def spmd(flat, ti, tw, w1_l, w2_l):
            out = moe_dispatch_sharded(
                flat, ti, tw, local_ffn(w1_l, w2_l), E // Rk,
                expert_capacity(N, E, k, 0.0), "ep",
            )
            return lax.psum(out, "ep")

        got = shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(), P(), P("ep"), P("ep")),
            out_specs=P(),
        )(flat, top_idx, top_w, w1, w2)
    else:
        def spmd(fl, ti, tw, w1_l, w2_l):
            out = moe_a2a(
                fl, ti, tw, local_ffn(w1_l, w2_l), E,
                expert_capacity(N // Rk, E, k, 0.0), "ep",
            )
            return out

        got = shard_map(
            spmd, mesh=mesh,
            in_specs=(P("ep"), P("ep"), P("ep"), P("ep"), P("ep")),
            out_specs=P("ep"),
        )(flat, top_idx, top_w, w1, w2)

    ref = _dense_ref(
        flat, np.asarray(top_idx), np.asarray(top_w),
        lambda e, x: np.maximum(x @ np.asarray(w1[e]), 0.0) @ np.asarray(w2[e]),
    )
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=2e-5)


def test_resolve_moe_impl():
    """(impl, rows, ranks, a grouped closure supplied, the share of the
    held experts the rows choose): `auto` is exact, chosen on one rank by
    the ridge and, under it, by that share; capacity paths only by name
    (the cases by shape: tests/test_moe_grouped.py)."""
    assert resolve_moe_impl("dense", 10_000, 4, True) == "dense"  # explicit wins
    assert resolve_moe_impl("auto", 8, 1, True) == "dense"  # decode-size, share not known
    assert resolve_moe_impl("auto", 8, 1, True, 0.9) == "dense"  # .. 16 onto 8
    assert resolve_moe_impl("auto", 8, 1, True, 0.06) == "grouped"  # .. 16 onto 256
    assert resolve_moe_impl("auto", 256, 1, True) == "dense"  # on the ridge
    assert resolve_moe_impl("auto", 257, 1, True) == "grouped"
    assert resolve_moe_impl("auto", 257, 1, False) == "dense"  # no closure
    assert resolve_moe_impl("auto", 4096, 4, True) == "dense"  # several ranks
    assert resolve_moe_impl("dispatch", 4096, 1, True) == "dispatch"
    assert resolve_moe_impl("a2a", 4096, 4, True) == "a2a"


@pytest.fixture(scope="module")
def gpt_oss_dir(tmp_path_factory):
    from tests.fakes.checkpoints import make_tiny_gpt_oss

    d = tmp_path_factory.mktemp("gpt_oss_moe")
    make_tiny_gpt_oss(d)
    return d


@pytest.fixture(scope="module")
def deepseek_dir(tmp_path_factory):
    from tests.fakes.checkpoints import make_tiny_deepseek_v2

    d = tmp_path_factory.mktemp("deepseek_moe")
    make_tiny_deepseek_v2(d)
    return d


def _engine_logits(model_dir, impl, ids):
    """Fresh engine per impl: the moe path branches at trace time, so a
    shared engine's jit cache would mask the second impl."""
    from dnet_tpu.core.engine import LocalEngine

    eng = LocalEngine(model_dir, max_seq=64, param_dtype="float32")
    eng.model.moe_impl = impl
    eng.model.moe_capacity_factor = 0.0  # exact: no capacity drops
    out = np.asarray(eng.prefill("n", ids), np.float32)
    eng.end_session("n")
    return out


def test_gpt_oss_mesh_a2a_matches_dense(gpt_oss_dir, eight_devices):
    """all_to_all expert parallelism through the full mesh program: a2a
    prefill + decode == exact dense single-device."""
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.core.types import DecodingParams
    from dnet_tpu.parallel.engine import MeshEngine

    ids = [1] + list(range(40, 72))
    local = LocalEngine(gpt_oss_dir, max_seq=64, param_dtype="float32")
    ref_logits = np.asarray(local.prefill("a", ids), np.float32)
    local.end_session("a")
    ref_toks = [
        r.token_id
        for r in local.generate(ids, DecodingParams(temperature=0.0), max_tokens=6)
    ]

    eng = MeshEngine(gpt_oss_dir, pp=2, tp=2, max_seq=64, param_dtype="float32")
    eng.model.moe_impl = "a2a"
    eng.model.moe_capacity_factor = 0.0  # exact: no capacity drops
    got_logits = np.asarray(eng.prefill("b", ids), np.float32)
    eng.end_session("b")
    np.testing.assert_allclose(got_logits, ref_logits, atol=1e-4, rtol=1e-4)
    got_toks = [
        r.token_id
        for r in eng.generate(ids, DecodingParams(temperature=0.0), max_tokens=6)
    ]
    assert got_toks == ref_toks


def test_deepseek_mesh_a2a_matches_dense(deepseek_dir, eight_devices):
    """DeepSeek routed experts through a2a EP on the segmented mesh ring."""
    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.parallel.engine import MeshEngine

    ids = [1] + list(range(40, 72))
    local = LocalEngine(deepseek_dir, max_seq=64, param_dtype="float32")
    ref = np.asarray(local.prefill("a", ids), np.float32)
    local.end_session("a")

    eng = MeshEngine(deepseek_dir, pp=2, tp=2, max_seq=64, param_dtype="float32")
    eng.model.moe_impl = "a2a"
    eng.model.moe_capacity_factor = 0.0  # exact: no capacity drops
    got = np.asarray(eng.prefill("b", ids), np.float32)
    eng.end_session("b")
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("family_dir", ["gpt_oss_dir", "deepseek_dir"])
def test_engine_dispatch_matches_dense(family_dir, request):
    """Engine-level: dispatch prefill logits == dense prefill logits."""
    model_dir = request.getfixturevalue(family_dir)
    ids = [1] + list(range(40, 79))  # 40 tokens: prefill-size routing
    dense = _engine_logits(model_dir, "dense", ids)
    disp = _engine_logits(model_dir, "dispatch", ids)
    np.testing.assert_allclose(disp, dense, rtol=2e-4, atol=2e-4)
