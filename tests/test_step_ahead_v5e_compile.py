"""The chained decode step, held to what the v5e compiler makes of it.

The served path keeps one decode step in flight ahead of the one it reads
(sched/step.py): step n+1 takes the chained lanes' input tokens from step
n's result on the device.  That select lives INSIDE the step's program, as
one more argument of the program every step already was (core/batch.py:
_chain_tokens): the engine's own `_ragged_step`, taken with the arguments a
chained launch gives it and compiled ahead of time for the chip at the rag
cell's geometry (a block pool, 32 slots) and at the gen cell's rehearsal
shape (a state entry a lane), must hold

- the select, over the [slots, 1] token rows;
- NO `copy` as large as a pool leaf or a state leaf (one more argument must
  not cost the pool its in-place read, nor the state its in-place update);

and a chained launch must run that ONE program, the same compiled program
as a launch with no flight before it.

ONE file, topology inside a fixture (on-chip-measurement guide, section 2):
only the worker given this file loads the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnet_tpu.obs import metric
from dnet_tpu.obs.phases import KV_KIND_FULL
from tests.test_pool_layout_v5e_compile import (  # noqa: F401  (fixtures)
    _abstract,
    no_cache,
    one_chip,
)


def _chained_step_args(eng, nonce, prompt):
    """Serve `nonce` one step with no flight before it and one chained to
    that: (the arguments the chained launch gave `_ragged_step`, how many
    programs each launch ran, how many compiles the second one cost)."""
    from dnet_tpu.core.batch import CHAINED, DecodeFlight
    from dnet_tpu.core.types import DecodingParams

    calls, step = [], eng._ragged_step
    eng._ragged_step = lambda *a: calls.append(a) or step(*a)
    dec = DecodingParams(temperature=0.7, top_p=0.9, seed=3)
    res = eng.prefill_and_sample(nonce, prompt, dec)
    reqs = {nonce: (int(res.token[0]), dec)}
    compiles = metric("dnet_jit_compiles_total").labels(fn="paged_attend")
    first = eng.decode_launch(reqs, chain=DecodeFlight())
    c0 = compiles.value
    second = eng.decode_launch(reqs, budgets={nonce: 5}, chain=first)
    assert second.chained == {nonce} and len(calls) == 2  # one program a launch
    assert compiles.value == c0  # and the same one
    for flight in (first, second):
        out, errs = eng.decode_read(flight)
        assert not errs and set(out) == {nonce}
    eng._ragged_step = step
    args = calls[1]
    slot = eng.slot_of[nonce]
    assert args[2][slot, 0] == CHAINED  # the host row says: from the device
    assert args[10] is first.src.token  # step n's tokens, not read yet
    return args


def _compiled(program, args):
    return program.trace(*args).lower(lowering_platforms=("tpu",)).compile()


def _copies_at_least(text: str, elems: int):
    """The program's `copy` instructions with at least `elems` elements.
    (`copy-start` / `copy-done` are the compiler moving a small array into
    its faster memory space and back, which it does to the rehearsal
    model's 150 kB of state: no relayout, and not counted.)"""
    found = []
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?copy[.\d]*\s*=\s*\w+\[([\d,]+)\]", text, re.M):
        if int(np.prod([int(d) for d in m.group(1).split(",")])) >= elems:
            found.append(m.group(0).strip())
    return found


def _selects_the_tokens(text: str, slots: int) -> bool:
    return re.search(rf"s32\[{slots},1\]\S*\s+select\(", text) is not None


def test_the_chained_step_over_a_block_pool_at_the_rag_geometry(
    one_chip, no_cache, tmp_path, monkeypatch
):
    """The 32-slot step over llama's scan, 6 layers, 8192 blocks of 16,
    4 x 128: one program with the select in it, the pool read through the
    Mosaic kernel and nothing else."""
    from tests.fakes.checkpoints import make_tiny_llama

    from dnet_tpu.config import reset_settings_cache
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.ops import paged_attention

    L, N, bt, KVH, Hd, slots, max_seq = 6, 8192, 16, 4, 128, 32, 4096
    make_tiny_llama(
        tmp_path, {"num_hidden_layers": L, "num_attention_heads": 32,
                   "num_key_value_heads": KVH, "head_dim": Hd},
    )
    monkeypatch.setenv("DNET_KV_BLOCK_TOKENS", str(bt))
    monkeypatch.setenv("DNET_KV_POOL_BLOCKS", "8")  # here; N on the described chip
    reset_settings_cache()
    eng = BatchedEngine(
        tmp_path, slots=slots, max_seq=max_seq, param_dtype="bfloat16",
        kv_dtype="bfloat16", kv_paged=True,
    )
    try:
        seen = _chained_step_args(eng, "a", list(range(300, 320)))
        monkeypatch.setattr(paged_attention, "paged_attend_impl", lambda: "pallas")
        eng._build_ragged()
        step = _abstract(seen, one_chip, 3, N)
        step[4] = {KV_KIND_FULL: jax.ShapeDtypeStruct((slots, max_seq // bt), jnp.int32,
                                                      sharding=one_chip)}
        text = _compiled(eng._ragged_step, step).as_text()
        assert "tpu_custom_call" in text
        assert _selects_the_tokens(text, slots)
        assert _copies_at_least(text, L * N * bt * KVH * Hd) == []
    finally:
        eng.close()
        reset_settings_cache()


def test_the_chained_step_over_a_state_store_at_the_gen_rehearsal_shape(
    one_chip, no_cache, tmp_path, monkeypatch
):
    """The gen cell's rehearsal model (brumby: 2 layers, 2 KV heads of 16,
    4 lanes): the state rides the step donated and is updated in place by
    the retention kernel, with the select beside it in the same program."""
    from tests.fakes.checkpoints import make_tiny_brumby

    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.kv import StateStore
    from dnet_tpu.ops import paged_attention

    slots = 4
    make_tiny_brumby(tmp_path)
    eng = BatchedEngine(tmp_path, slots=slots, max_seq=256, param_dtype="bfloat16")
    try:
        assert isinstance(eng.kv_store, StateStore)
        seen = _chained_step_args(eng, "a", list(range(30, 50)))
        leaves = jax.tree.leaves(seen[3])
        monkeypatch.setattr(paged_attention, "paged_attend_impl", lambda: "pallas")
        eng._build_ragged()
        compiled = _compiled(eng._ragged_step, _abstract(seen, one_chip))
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "retention_step" in text
        assert _selects_the_tokens(text, slots)
        assert _copies_at_least(text, max(int(np.prod(a.shape)) for a in leaves)) == []
        # the whole store goes in and comes out in place: no second copy
        state = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= state and mem.temp_size_in_bytes < state
    finally:
        eng.close()
