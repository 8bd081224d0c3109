"""The absorbed latent-attention decode kernel (ops/paged_attention.py:
paged_attend_latent) in `interpret` and `emulate` against plain jnp, float32
at a small shape (4 heads over an entry of 16 + 8 kept in 128 lanes, blocks
of 8): ragged lengths, a lane of length 0, one at a block's edge and one at
the table's end, the current token's entry folded in the launch, the layer
taken by index from the stack, several table entries a grid step; and the
absorb itself: absorbed queries through the kernel and W_kvb[V] after it
equal attention over keys and values EXPANDED from the same latents, to
float32 rounding.  The store's side: the latent leaf's commit, gather and
row append."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.ops import paged_attention as P

H, R, ROPE, NOPE, VD, W, BT = 4, 16, 8, 16, 12, 128, 8
IMPLS = ("emulate", "interpret")
TOL = 2e-6  # float32, outputs of size ~0.5 (measured 1e-7)
HP = jax.lax.Precision.HIGHEST


def case(nb, lens, layers=2, seed=41):
    """A pool of `layers`, every lane its own shuffled blocks, queries and
    the current token's entries."""
    B = len(lens)
    key = jax.random.split(jax.random.key(seed), 4)
    N = B * nb + 1
    entries = jax.random.normal(key[0], (layers, N, BT, R + ROPE)) * 0.5
    pool = jnp.pad(entries, ((0, 0), (0, 0), (0, 0), (0, W - R - ROPE)))
    perm = np.random.default_rng(seed).permutation(N - 1)[: B * nb].reshape(B, nb) + 1
    q = jnp.pad(jax.random.normal(key[1], (B, 1, H, R + ROPE)), ((0, 0),) * 3 + ((0, W - R - ROPE),))
    c_new = jnp.pad(
        jax.random.normal(key[2], (B, 1, R + ROPE)) * 0.5, ((0, 0), (0, 0), (0, W - R - ROPE))
    )
    return pool, jnp.asarray(perm, jnp.int32), jnp.asarray(lens, jnp.int32), q, c_new


def definition(pool_l, tables, pos, q, c_new):
    """softmax_j(q . entry_j) entry_j[:R] over j <= pos, the new entry at pos."""
    B, nb = tables.shape
    view = pool_l[tables].reshape(B, nb * BT, W)
    view = jax.vmap(lambda v, e, p: jax.lax.dynamic_update_slice(v, e, (p, 0)))(view, c_new, pos)
    s = jnp.einsum("bhw,bsw->bhs", q[:, 0], view, precision=HP)
    live = jnp.arange(nb * BT)[None, None, :] <= pos[:, None, None]
    pr = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsr->bhr", pr, view[..., :R], precision=HP)


# a table of 4 entries folds four a grid step, of 6 two, of 5 one
@pytest.mark.parametrize("nb", [4, 6, 5, 16])
@pytest.mark.parametrize("impl", IMPLS)
def test_the_kernel_equals_the_definition(impl, nb):
    S = nb * BT
    lens = [0, 1, BT, 2 * BT - 1, S - 1, S // 2 + 3]  # idle, one token, a block's edge, ..., the table's end
    pool, tables, pos, q, c_new = case(nb, lens)
    for layer in range(2):
        got = P.paged_attend_latent(q, pool, tables, pos, c_new, R, jnp.int32(layer), impl=impl)
        assert got.shape == (len(lens), 1, H, R)
        want = definition(pool[layer], tables, pos, q, c_new)
        assert float(jnp.max(jnp.abs(got[:, 0] - want))) < TOL
    # the lane of length 0 attends its own token alone: its output IS the entry
    assert float(jnp.max(jnp.abs(got[0, 0] - c_new[0, :, :R]))) < TOL


@pytest.mark.parametrize("impl", IMPLS)
def test_the_layer_is_taken_by_index_and_dead_entries_are_never_read(impl):
    pool, tables, pos, q, c_new = case(4, [5, 11, 0])
    a = P.paged_attend_latent(q, pool, tables, pos, c_new, R, jnp.int32(0), impl=impl)
    b = P.paged_attend_latent(q, pool, tables, pos, c_new, R, jnp.int32(1), impl=impl)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3  # another layer's entries
    # blocks past each lane's length hold NaN: a kernel that read them would say so
    dead = np.ones((3, 4), bool)
    for lane, n in enumerate([5, 11, 0]):
        dead[lane, : -(-n // BT)] = False
    poisoned = pool.at[:, np.asarray(tables)[dead]].set(jnp.nan)
    # ... and the rows past the length inside the last live block hold stale,
    # large content (finite, as a pool's rows are: they are masked, not skipped)
    for lane, n in enumerate([5, 11]):
        blk = int(tables[lane, n // BT])
        poisoned = poisoned.at[:, blk, n % BT:].set(1e4)
    if impl == "emulate":
        # the twin gathers whole tables and masks: NaN x 0 is NaN there
        poisoned = jnp.nan_to_num(poisoned, nan=1e4)
    c = P.paged_attend_latent(q, poisoned, tables, pos, c_new, R, jnp.int32(0), impl=impl)
    assert float(jnp.max(jnp.abs(a - c))) < TOL


@pytest.mark.parametrize("impl", IMPLS)
def test_absorbed_equals_expanded(impl):
    """q_abs_h = W_kvb,h[K]^T q_nope_h through the kernel, W_kvb,h[V] after
    it: the same outputs as attention over per-head keys and values made
    from the latents."""
    nb, lens = 6, [0, 7, 24, 47, 30]
    pool, tables, pos, _, c_new = case(nb, lens, seed=7)
    B = len(lens)
    key = jax.random.split(jax.random.key(8), 3)
    w_kvb = jax.random.normal(key[0], (R, H, NOPE + VD)) * R**-0.5
    q_nope = jax.random.normal(key[1], (B, H, NOPE))
    q_pe = jax.random.normal(key[2], (B, H, ROPE))
    sigma = (NOPE + ROPE) ** -0.5
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, w_kvb[..., :NOPE], precision=HP)
    q_lat = jnp.concatenate([q_abs, q_pe, jnp.zeros((B, H, W - R - ROPE))], -1) * sigma
    o_lat = P.paged_attend_latent(
        q_lat[:, None], pool, tables, pos, c_new, R, jnp.int32(1), impl=impl
    )[:, 0]
    got = jnp.einsum("bhr,rhv->bhv", o_lat, w_kvb[..., NOPE:], precision=HP)

    view = pool[1][tables].reshape(B, nb * BT, W)
    view = jax.vmap(lambda v, e, p: jax.lax.dynamic_update_slice(v, e, (p, 0)))(view, c_new, pos)
    kv = jnp.einsum("bsr,rhn->bshn", view[..., :R], w_kvb, precision=HP)
    s = (
        jnp.einsum("bhn,bshn->bhs", q_nope, kv[..., :NOPE], precision=HP)
        + jnp.einsum("bhd,bsd->bhs", q_pe, view[..., R:R + ROPE], precision=HP)
    ) * sigma
    live = jnp.arange(nb * BT)[None, None, :] <= pos[:, None, None]
    want = jnp.einsum(
        "bhs,bshv->bhv", jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1), kv[..., NOPE:],
        precision=HP,
    )
    assert float(jnp.max(jnp.abs(got - want))) < 5 * TOL


def test_the_dispatcher_books_its_selection_and_refuses_a_stray_impl():
    from dnet_tpu.ops.kernel_select import KERNELS, SELECTIONS

    assert P.PAGED_LATENT_NAME in KERNELS
    pool, tables, pos, q, c_new = case(4, [3])
    before = SELECTIONS.snapshot()[P.PAGED_LATENT_NAME]["emulate"]
    P.paged_attend_latent(q, pool, tables, pos, c_new, R, 0, impl="emulate")
    assert SELECTIONS.snapshot()[P.PAGED_LATENT_NAME]["emulate"] == before + 1
    with pytest.raises(ValueError, match="not in"):
        P.paged_attend_latent(q, pool, tables, pos, c_new, R, 0, impl="dense")


# ---- the store: a pool whose leaves are the model's ----------------------------


class _LatentModel:
    """As much of a latent model as KindStore asks for."""

    paged_kinds = None
    layers = [0, 1]
    latent_rank = R

    def pool_leaves(self):
        return {"c": (1, W)}

    def init_kv(self, n, batch, max_seq, dtype="float32", quant_bits=0, rotating=True):
        return {"c": jnp.zeros((n, batch, max_seq, 1, W), jnp.dtype(dtype))}


def _store(pool_blocks=12):
    from dnet_tpu.kv import KindStore, PagedKVConfig

    cfg = PagedKVConfig(block_tokens=BT, pool_blocks=pool_blocks)
    return KindStore(_LatentModel(), {"full": cfg}, "float32", session_tokens=4 * BT)


def test_the_store_takes_its_leaves_from_the_model():
    store = _store()
    assert store.leaves == {"c": (1, W)} and store.latent_rank == R
    assert {k: v.shape for k, v in store.kv["full"].items()} == {"c": (2, 12, BT, W)}
    # a staged latent row's blocks into the pool, and back out of it
    row = {"c": jax.random.normal(jax.random.key(0), (2, 1, 4 * BT, 1, W))}
    store.commit_staged(row, {"full": ([0, 1, 2], [7, 3, 9])})
    back = store.gather_row([7, 3, 9], 4 * BT)
    assert back["c"].shape == (2, 1, 4 * BT, 1, W)
    assert bool(jnp.all(back["c"][:, :, : 3 * BT] == row["c"][:, :, : 3 * BT]))
    # one new entry a lane: lane 0 into block 9 row 2, lane 1 dropped (past the pool)
    new = {"c": jnp.ones((2, 2, 1, W))}
    store.append_rows(new, {"full": [9, 12]}, [2, 0])
    pool = store.kv["full"]["c"]
    assert bool(jnp.all(pool[:, 9, 2] == 1.0)) and float(jnp.sum(pool == 1.0)) == 2 * W


def test_a_model_of_keys_and_values_keeps_the_pool_it_had():
    """The default leaves: k and v of KVH * Hd, to the byte (PR 38's layout)."""
    from types import SimpleNamespace

    from dnet_tpu.kv import KindStore, PagedKVConfig

    model = SimpleNamespace(
        paged_kinds=None, layers=[0, 1, 2],
        config=SimpleNamespace(num_key_value_heads=4, head_dim=128),
    )
    store = KindStore(model, {"full": PagedKVConfig(16, 32)}, "bfloat16")
    assert store.leaves == {"k": (4, 128), "v": (4, 128)} and store.latent_rank == 0
    assert {k: (v.shape, v.dtype.name) for k, v in store.kv["full"].items()} == {
        "k": ((3, 32, 16, 512), "bfloat16"), "v": ((3, 32, 16, 512), "bfloat16"),
    }
