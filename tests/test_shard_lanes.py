"""Batched lanes over the ring (VERDICT r4 next #4, shard/lanes.py).

Coalesced multi-lane decode frames through a ShardCompute chain must
reproduce every member's SOLO stream byte-for-byte — greedy and seeded
sampling alike — because lane adoption carries the session's RNG key,
repetition counts, and position into the pool unchanged.
"""

import numpy as np
import pytest

from dnet_tpu.core.types import ActivationMessage, DecodingParams

pytestmark = [pytest.mark.shard]


def _mk_shards(tiny_llama_dir, lanes):
    from dnet_tpu.shard.compute import ShardCompute

    lo = ShardCompute(
        tiny_llama_dir, [0, 1], max_seq=64, param_dtype="float32",
        wire_dtype="float32", lanes=lanes,
    )
    hi = ShardCompute(
        tiny_llama_dir, [2, 3], max_seq=64, param_dtype="float32",
        wire_dtype="float32", lanes=lanes,
    )
    return lo, hi


def _prefill(shards, nonce, ids, dec):
    arr = np.asarray([ids], dtype=np.int32)
    msg = ActivationMessage(
        nonce=nonce, layer_id=-1, seq=0, dtype="tokens", shape=arr.shape,
        data=arr.tobytes(), pos=0, decoding=dec,
    )
    for sc in shards:
        msg = sc.process(msg)
    assert msg.is_final
    return msg.token_id


def _solo_stream(tiny_llama_dir, ids, dec, n):
    """Reference: one request through a lane-free chain."""
    shards = _mk_shards(tiny_llama_dir, lanes=0)
    toks = [_prefill(shards, "solo", ids, dec)]
    pos = len(ids)
    for step in range(1, n):
        arr = np.asarray([[toks[-1]]], dtype=np.int32)
        msg = ActivationMessage(
            nonce="solo", layer_id=-1, seq=step, dtype="tokens",
            shape=arr.shape, data=arr.tobytes(), pos=pos, decoding=dec,
        )
        for sc in shards:
            msg = sc.process(msg)
        assert msg.is_final
        toks.append(msg.token_id)
        pos += 1
    for sc in shards:
        sc.engine.close()
    return toks


def _batch_frame(members, seq):
    """members: list of (nonce, token, pos, dec)."""
    from dataclasses import asdict

    tokens = np.asarray([[t] for _, t, _, _ in members], dtype=np.int32)
    return ActivationMessage(
        nonce="__lanes__", layer_id=-1, seq=seq, dtype="tokens",
        shape=tokens.shape, data=tokens.tobytes(), pos=0,
        lanes=[
            {"nonce": n, "seq": seq, "pos": p, "decoding": asdict(d)}
            for n, t, p, d in members
        ],
    )


@pytest.mark.parametrize("greedy", [True, False])
def test_lane_streams_match_solo(tiny_llama_dir, greedy):
    """4 concurrent nonces, mixed prompts (and mixed seeds when sampling),
    decoded via coalesced batch frames == each nonce's solo stream."""
    n_tok = 6
    prompts = {
        "a": [256, 72, 101],
        "b": [256, 84, 104, 101],
        "c": [7, 3, 11, 7, 3],
        "d": [256, 110],
    }
    decs = {
        n: (
            DecodingParams(temperature=0.0)
            if greedy
            else DecodingParams(temperature=0.8, top_p=0.9, seed=41 + i)
        )
        for i, n in enumerate(prompts)
    }
    want = {
        n: _solo_stream(tiny_llama_dir, prompts[n], decs[n], n_tok)
        for n in prompts
    }

    shards = _mk_shards(tiny_llama_dir, lanes=4)
    got = {n: [_prefill(shards, n, prompts[n], decs[n])] for n in prompts}
    pos = {n: len(prompts[n]) for n in prompts}
    for step in range(1, n_tok):
        members = [(n, got[n][-1], pos[n], decs[n]) for n in prompts]
        msg = _batch_frame(members, step)
        for sc in shards:
            msg = sc.process(msg)
        assert msg.is_final and msg.lane_finals is not None
        by_nonce = {f["nonce"]: f for f in msg.lane_finals}
        for n in prompts:
            got[n].append(int(by_nonce[n]["token_id"]))
            pos[n] += 1
    for sc in shards:
        sc.engine.close()
    assert got == want


def test_partial_batch_and_leavers(tiny_llama_dir):
    """Members may leave (EOS'd request): later batch frames with a subset
    of lanes keep the remaining members' streams exact."""
    n_tok = 6
    prompts = {"a": [256, 72, 101], "b": [7, 3, 11, 7]}
    dec = DecodingParams(temperature=0.0)
    want = {
        n: _solo_stream(tiny_llama_dir, prompts[n], dec, n_tok)
        for n in prompts
    }
    shards = _mk_shards(tiny_llama_dir, lanes=4)
    got = {n: [_prefill(shards, n, prompts[n], dec)] for n in prompts}
    pos = {n: len(prompts[n]) for n in prompts}
    for step in range(1, n_tok):
        live = list(prompts) if step < 3 else ["b"]  # "a" leaves after step 2
        members = [(n, got[n][-1], pos[n], dec) for n in live]
        msg = _batch_frame(members, step)
        for sc in shards:
            msg = sc.process(msg)
        by_nonce = {f["nonce"]: f for f in msg.lane_finals}
        for n in live:
            got[n].append(int(by_nonce[n]["token_id"]))
            pos[n] += 1
    for sc in shards:
        sc.engine.close()
    assert got["a"] == want["a"][:3]
    assert got["b"] == want["b"]


def test_single_shard_ring_lanes(tiny_llama_dir):
    """A one-shard ring (head == tail) takes the fused token->sample lane
    program; streams still match solo."""
    from dnet_tpu.shard.compute import ShardCompute

    dec = DecodingParams(temperature=0.0)
    want = _solo_stream(tiny_llama_dir, [256, 72, 101], dec, 5)
    sc = ShardCompute(
        tiny_llama_dir, [0, 1, 2, 3], max_seq=64, param_dtype="float32",
        wire_dtype="float32", lanes=2,
    )
    got = [_prefill([sc], "x", [256, 72, 101], dec)]
    # second member keeps the batch genuinely multi-lane
    other = [_prefill([sc], "y", [7, 3, 11], dec)]
    pos = {"x": 3, "y": 3}
    for step in range(1, 5):
        msg = _batch_frame(
            [("x", got[-1], pos["x"], dec), ("y", other[-1], pos["y"], dec)],
            step,
        )
        msg = sc.process(msg)
        by_nonce = {f["nonce"]: f for f in msg.lane_finals}
        got.append(int(by_nonce["x"]["token_id"]))
        other.append(int(by_nonce["y"]["token_id"]))
        pos["x"] += 1
        pos["y"] += 1
    sc.engine.close()
    assert got == want


@pytest.fixture(scope="module")
def tiny_moe_dirs(tmp_path_factory):
    from tests.fakes import checkpoints as ck

    make = {"qwen3_moe": ck.make_tiny_qwen3_moe, "mixtral": ck.make_tiny_mixtral,
            "deepseek_v2": ck.make_tiny_deepseek_v2}
    made = {}

    def get(family):
        if family not in made:
            made[family] = tmp_path_factory.mktemp(f"lanes_{family}")
            make[family](made[family])
        return made[family]

    return get


@pytest.mark.parametrize("split", [True, False], ids=["head-tail", "one-shard"])
@pytest.mark.parametrize("family", ["qwen3_moe", "mixtral", "deepseek_v2"])
def test_moe_lanes_keep_the_dense_einsum_and_match_solo(family, split, tiny_moe_dirs, monkeypatch):
    """The pool vmaps a ONE-row window over its lanes.  Alone that row (top-2
    of 4 experts: 0.44 of them chosen) goes grouped, as the solo session's
    step does; under the vmap no grouped closure may be traced (neither
    `lax.ragged_dot` nor the kernel batches over lanes, and the batched
    einsum reads the experts once for all lanes).  The pool says nothing
    (ops/moe.py: whole_batch) and so keeps the einsum: head, tail and the
    fused one-shard program, with the solo streams' tokens."""
    from dnet_tpu.ops import moe
    from dnet_tpu.shard.compute import ShardCompute

    d = tiny_moe_dirs(family)
    calls, real = [], moe.grouped_matmul
    monkeypatch.setattr(
        moe, "grouped_matmul", lambda *a, **kw: calls.append(1) or real(*a, **kw)
    )

    def shards(lanes):
        spans = ([0, 1], [2, 3]) if split else ([0, 1, 2, 3],)
        return [
            ShardCompute(d, span, max_seq=64, param_dtype="float32",
                         wire_dtype="float32", lanes=lanes)
            for span in spans
        ]

    dec = DecodingParams(temperature=0.0)
    prompts = {"x": [256, 72, 101], "y": [7, 3, 11, 5]}
    n_tok = 5

    def step(chain, members, seq):
        msg = _batch_frame(members, seq) if len(members) > 1 else None
        if msg is None:
            (n, tok, pos, _), = members
            arr = np.asarray([[tok]], dtype=np.int32)
            msg = ActivationMessage(
                nonce=n, layer_id=-1, seq=seq, dtype="tokens", shape=arr.shape,
                data=arr.tobytes(), pos=pos, decoding=dec,
            )
        for sc in chain:
            msg = sc.process(msg)
        assert msg.is_final
        if msg.lane_finals is None:
            return {members[0][0]: msg.token_id}
        return {f["nonce"]: int(f["token_id"]) for f in msg.lane_finals}

    want = {}
    for n, ids in prompts.items():  # solo: a lane-free chain, one row a step
        chain = shards(0)
        want[n] = [_prefill(chain, n, ids, dec)]
        before = len(calls)
        for s in range(1, n_tok):
            want[n].append(step(chain, [(n, want[n][-1], len(ids) + s - 1, dec)], s)[n])
        assert len(calls) > before  # the solo step's one row went grouped
        for sc in chain:
            sc.engine.close()

    chain = shards(2)
    got = {n: [_prefill(chain, n, ids, dec)] for n, ids in prompts.items()}
    before = len(calls)
    for s in range(1, n_tok):
        out = step(chain, [(n, got[n][-1], len(prompts[n]) + s - 1, dec) for n in prompts], s)
        for n in prompts:
            got[n].append(out[n])
    assert len(calls) == before  # nothing grouped was traced under the lanes' vmap
    for sc in chain:
        sc.engine.close()
    assert got == want


def test_faulted_lane_fails_alone(tiny_llama_dir):
    """A bad member (stale pos / reset race) is flagged and error-failed
    ALONE; its batchmate's stream continues exactly."""
    n_tok = 4
    dec = DecodingParams(temperature=0.0)
    want_b = _solo_stream(tiny_llama_dir, [7, 3, 11, 7], dec, n_tok)
    shards = _mk_shards(tiny_llama_dir, lanes=2)
    tok_a = _prefill(shards, "a", [256, 72], dec)
    got_b = [_prefill(shards, "b", [7, 3, 11, 7], dec)]
    pos_b = 4
    for step in range(1, n_tok):
        # member "a" carries a stale pos every step; "b" stays healthy
        msg = _batch_frame(
            [("a", tok_a, 99, dec), ("b", got_b[-1], pos_b, dec)], step
        )
        for sc in shards:
            msg = sc.process(msg)
        assert msg.is_final
        by_nonce = {f["nonce"]: f for f in msg.lane_finals}
        assert by_nonce["a"]["token_id"] == -1 and by_nonce["a"]["error"]
        assert not by_nonce["b"].get("error")
        got_b.append(int(by_nonce["b"]["token_id"]))
        pos_b += 1
    for sc in shards:
        sc.engine.close()
    assert got_b == want_b


def test_unknown_nonce_lane_fails_alone(tiny_llama_dir):
    """A member with no prefilled session (cancelled before its batch
    frame landed) faults alone at adoption."""
    dec = DecodingParams(temperature=0.0)
    shards = _mk_shards(tiny_llama_dir, lanes=2)
    tok = _prefill(shards, "live", [256, 72], dec)
    msg = _batch_frame([("ghost", 5, 3, dec), ("live", tok, 2, dec)], 1)
    for sc in shards:
        msg = sc.process(msg)
    by_nonce = {f["nonce"]: f for f in msg.lane_finals}
    assert by_nonce["ghost"]["token_id"] == -1 and by_nonce["ghost"]["error"]
    assert by_nonce["live"]["token_id"] >= 0
    for sc in shards:
        sc.engine.close()


def test_all_faulted_batch_frame_yields_per_lane_errors(tiny_llama_dir):
    """A batch frame whose EVERY member faulted (mass reset race: no
    session to adopt on any lane) must still come back as per-member error
    finals.  The empty `good` list used to build float64 index arrays
    (`np.asarray([])`) that TypeError'd the whole frame on the mid shard —
    hiding the real per-lane errors behind a frame-level crash."""
    dec = DecodingParams(temperature=0.0)
    shards = _mk_shards(tiny_llama_dir, lanes=2)
    # prime the pools so adoption paths are live, then use never-prefilled
    # nonces: both members fault at adoption on the head shard
    _prefill(shards, "warm", [256, 72], dec)
    msg = _batch_frame([("g1", 5, 3, dec), ("g2", 6, 4, dec)], 1)
    for sc in shards:
        msg = sc.process(msg)
    assert msg.is_final
    assert len(msg.lane_finals) == 2
    for f in msg.lane_finals:
        assert f["token_id"] == -1 and f["error"], f
    # the pool is undamaged: a healthy member still decodes afterwards
    msg = _batch_frame([("warm", 7, 2, dec)], 1)
    for sc in shards:
        msg = sc.process(msg)
    assert msg.lane_finals[0]["token_id"] >= 0
    for sc in shards:
        sc.engine.close()


def test_lane_frame_wire_roundtrip():
    """The lanes metadata survives the msgpack frame encoding."""
    from dnet_tpu.transport.protocol import ActivationFrame

    f = ActivationFrame(
        nonce="__lanes__", seq=3, layer_id=-1, pos=0, dtype="tokens",
        shape=(2, 1), payload=b"\x01\x00\x00\x00\x02\x00\x00\x00",
        lanes=[
            {"nonce": "a", "seq": 3, "pos": 7, "decoding": {"temperature": 0.0}},
            {"nonce": "b", "seq": 2, "pos": 5, "decoding": {"temperature": 0.8}},
        ],
    )
    g = ActivationFrame.from_bytes(f.to_bytes())
    assert g.lanes == f.lanes
    m = g.to_message()
    assert m.lanes == f.lanes


def _drive_lane_batches(shards, prompts, decs, n_tok):
    """Prefill each nonce solo, then decode via coalesced batch frames."""
    got = {n: [_prefill(shards, n, prompts[n], decs[n])] for n in prompts}
    pos = {n: len(prompts[n]) for n in prompts}
    for step in range(1, n_tok):
        members = [(n, got[n][-1], pos[n], decs[n]) for n in prompts]
        msg = _batch_frame(members, step)
        for sc in shards:
            msg = sc.process(msg)
        by_nonce = {f["nonce"]: f for f in msg.lane_finals}
        for n in prompts:
            got[n].append(int(by_nonce[n]["token_id"]))
            pos[n] += 1
    for sc in shards:
        sc.engine.close()
    return got


def test_lanes_compose_with_mesh_shards(tiny_llama_dir, eight_devices):
    """Lanes x mesh-backed shards (the full north-star composition): each
    ring pass serves N nonces AND runs SPMD over the host's chips —
    shard_map(vmap) lane programs, per-lane pos/kv_commit inside the mesh
    program.  Streams equal solo."""
    from dnet_tpu.shard.compute import ShardCompute

    n_tok = 5
    prompts = {"a": [256, 72, 101], "b": [7, 3, 11, 7]}
    dec = DecodingParams(temperature=0.0)
    decs = {n: dec for n in prompts}
    want = {
        n: _solo_stream(tiny_llama_dir, prompts[n], dec, n_tok)
        for n in prompts
    }
    lo = ShardCompute(
        tiny_llama_dir, [0, 1], max_seq=64, param_dtype="float32",
        wire_dtype="float32", lanes=2, mesh_tp=2,
        mesh_devices=eight_devices[0:2],
    )
    hi = ShardCompute(
        tiny_llama_dir, [2, 3], max_seq=64, param_dtype="float32",
        wire_dtype="float32", lanes=2, mesh_tp=2,
        mesh_devices=eight_devices[2:4],
    )
    assert lo.lane_pool is not None and lo.engine.tp == 2
    got = _drive_lane_batches([lo, hi], prompts, decs, n_tok)
    assert got == want


def test_lanes_compose_with_sp_mesh_shard(tiny_llama_dir, eight_devices):
    """Lanes over an sp=2 mesh shard: per-lane KV shards its sequence axis
    while lanes batch the ring pass."""
    from dnet_tpu.shard.compute import ShardCompute

    n_tok = 5
    prompts = {"a": [256, 72, 101], "b": [11, 3, 7, 1]}
    dec = DecodingParams(temperature=0.0)
    decs = {n: dec for n in prompts}
    want = {
        n: _solo_stream(tiny_llama_dir, prompts[n], dec, n_tok)
        for n in prompts
    }
    lo = ShardCompute(
        tiny_llama_dir, [0, 1], max_seq=64, param_dtype="float32",
        wire_dtype="float32", lanes=2, mesh_tp=1, mesh_sp=2,
        mesh_devices=eight_devices[0:2],
    )
    hi = ShardCompute(
        tiny_llama_dir, [2, 3], max_seq=64, param_dtype="float32",
        wire_dtype="float32", lanes=2,
    )
    got = _drive_lane_batches([lo, hi], prompts, decs, n_tok)
    assert got == want


def test_lanes_mesh_seeded_sampling_parity(tiny_llama_dir, eight_devices):
    """Seeded SAMPLED lanes over a mesh shard: RNG/counts adoption keeps
    every stream byte-identical to its solo run."""
    from dnet_tpu.shard.compute import ShardCompute

    n_tok = 5
    prompts = {"a": [256, 72, 101], "b": [7, 3, 11]}
    decs = {
        "a": DecodingParams(temperature=0.8, top_p=0.9, seed=11),
        "b": DecodingParams(temperature=0.6, seed=12),
    }
    want = {
        n: _solo_stream(tiny_llama_dir, prompts[n], decs[n], n_tok)
        for n in prompts
    }
    lo = ShardCompute(
        tiny_llama_dir, [0, 1, 2, 3], max_seq=64, param_dtype="float32",
        wire_dtype="float32", lanes=2, mesh_tp=2,
        mesh_devices=eight_devices[0:2],
    )
    got = _drive_lane_batches([lo], prompts, decs, n_tok)
    assert got == want
