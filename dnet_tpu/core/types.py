"""Core DTOs shared across API and shard roles.

Covers the reference's core/types/messages.py (ActivationMessage, TokenResult,
StopCondition) and core/types/topology.py (LayerAssignment, TopologyInfo) with
a TPU-flavored device model: devices are keyed by (host, slice, chip) so the
solver can distinguish ICI-adjacent chips from DCN-separated hosts — the
analog of the reference's Thunderbolt-vs-LAN distinction
(src/dnet/core/types/topology.py:14-49).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np


def now_ms() -> float:
    return time.time() * 1000.0


class InferenceError(Exception):
    """Base of the typed serving errors (api/inference.py holds the rest;
    api/http.py maps each to a status)."""


class EngineCapabilityError(InferenceError):
    """The engine cannot serve the requested configuration — continuous
    batching over streamed weights, or a model without gated KV writes
    (raised by core/batch.py at LOAD time): maps to HTTP 422, an
    operator/config error, not a generic 500.  Defined here, below both
    layers, so that core/ raises it without importing api/."""


@dataclass
class DecodingParams:
    """Per-request sampling knobs carried alongside every token injection.

    Reference: src/dnet/core/decoding/config.py:4-14.
    """

    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    min_p: float = 0.0
    repetition_penalty: float = 1.0
    # top-p/min-p/top-k may never filter below this many candidates
    # (reference: core/decoding/config.py:4-14)
    min_tokens_to_keep: int = 1
    logprobs: bool = False
    top_logprobs: int = 0
    seed: Optional[int] = None
    # OpenAI logit_bias {token_id: additive bias in [-100, 100]}: the
    # reference carries the field but never applies it
    # (src/dnet/api/models.py:70 "NOTE: unused"); here it reaches sampling
    logit_bias: Optional[Dict[int, float]] = None
    # EOS ids for SHARD-side stop checks (ring self-continuation halts on
    # them without waiting for the API); sampling itself ignores this
    stop_token_ids: tuple = ()


@dataclass
class ActivationMessage:
    """In-memory activation envelope hopping shard-to-shard.

    dtype == "tokens" marks an int32 token-id payload entering layer 0
    (embedding happens on the shard); anything else is a hidden-state tensor.
    Reference: src/dnet/core/types/messages.py:50-101.
    """

    nonce: str
    layer_id: int  # last layer already applied; -1 = raw tokens
    seq: int  # per-nonce frame sequence number
    dtype: str
    shape: tuple
    data: Any = None  # np.ndarray | jax.Array | bytes
    pos: int = 0  # absolute position of first token in this frame
    callback_url: str = ""
    decoding: DecodingParams = field(default_factory=DecodingParams)
    is_final: bool = False
    token_id: Optional[int] = None
    logprob: Optional[float] = None
    top_logprobs: Optional[list] = None
    error: str = ""
    # ring self-continuation (decode grants): how many more tokens the tail
    # shard may feed back into the ring without an API round trip, and —
    # on a final message — the (token, pos, remaining_steps, next_seq)
    # continuation the adapter should inject at the head
    auto_steps: int = 0
    cont: Optional[tuple] = None
    # ring speculation: drafts ride a widened verify block head -> tail;
    # committed tokens ride the continuation tail -> head (hist commit);
    # extra_finals [(seq, token_id), ...] are the block's additional
    # accepted tokens, delivered as separate API callbacks by the adapter
    drafts: list = field(default_factory=list)
    committed: list = field(default_factory=list)
    extra_finals: Optional[list] = None
    # batched lanes (r5): a COALESCED decode frame serving several nonces in
    # one ring pass.  `lanes` rides every hop — one {"nonce","seq","pos",
    # "decoding"} entry per member, payload rows stacked in the same order;
    # the tail's final message answers with `lane_finals` (one TokenResult-
    # shaped dict per member) which the adapter fans out as per-nonce
    # SendToken callbacks.
    lanes: list = field(default_factory=list)
    lane_finals: Optional[list] = None
    # ring prefix caching (r5): the API (which alone sees token ids) keys
    # every store/hit.  A prompt frame with `prefix_store` asks each shard
    # to snapshot its post-prefill KV under that key; one with `prefix_hit`
    # seeds the session from the shard's snapshot (the frame then carries
    # only the SUFFIX tokens at pos = the snapshot length).
    prefix_store: str = ""
    prefix_hit: str = ""
    # end-to-end request deadline (epoch seconds, 0 = none): stamped by the
    # API's admission layer, rides every hop so ShardRuntime can drop an
    # expired frame at dequeue instead of burning compute on work nobody is
    # waiting for (dnet_tpu/admission/)
    deadline: float = 0.0
    # topology epoch the frame entered under (dnet_tpu/membership/):
    # carried across hops and stamped into the final token callback so the
    # epoch fence holds end to end.  0 = unfenced.
    epoch: int = 0
    # wire pipeline rx half (transport/wire_pipeline.py): the ingress path
    # launches H2D upload + on-device dequant for a QUEUED frame and
    # stashes the resulting device array here, so the compute thread finds
    # the payload already decoded (overlapped with the previous step's
    # compute).  Process-local only — never serialized onto the wire.
    device_data: Any = None
    # profiling timestamps (perf_counter seconds), reference messages.py:28-32
    t_recv: float = 0.0
    t_enq: float = 0.0
    t_tx_enq: float = 0.0

    @property
    def is_tokens(self) -> bool:
        return self.dtype == "tokens"

    def tokens(self) -> np.ndarray:
        if not self.is_tokens:
            raise ValueError("not a token message")
        if isinstance(self.data, (bytes, memoryview)):
            return np.frombuffer(self.data, dtype=np.int32).reshape(self.shape)
        return np.asarray(self.data, dtype=np.int32).reshape(self.shape)


@dataclass
class TokenResult:
    """Sampled token returned from the end shard to the API node."""

    nonce: str
    token_id: int
    logprob: Optional[float] = None
    top_logprobs: Optional[List[tuple]] = None  # [(token_id, logprob), ...]
    step: int = 0
    error: str = ""
    # topology epoch the emitting shard held (dnet_tpu/membership/);
    # 0 = unfenced.  The API drops results minted under a dead epoch.
    epoch: int = 0


@dataclass
class StopCondition:
    max_tokens: int = 256
    stop_token_ids: tuple = ()
    stop_sequences: tuple = ()


@dataclass
class DeviceInfo:
    """A participating device as seen by discovery + the solver."""

    instance: str  # unique shard instance name
    host: str  # reachable IP/hostname
    http_port: int
    grpc_port: int
    is_manager: bool = False
    # TPU placement: chips in the same (host, slice_id) share ICI.
    slice_id: int = 0
    chip_count: int = 1
    chip_kind: str = ""
    hbm_bytes: int = 0
    host_ram_bytes: int = 0
    flops_bf16: float = 0.0  # achieved matmul FLOP/s from microbench
    hbm_bw: float = 0.0  # bytes/s
    host_to_hbm_bw: float = 0.0  # bytes/s (device_put rate)
    t_comm: float = 0.0  # median seconds to next device for solver payloads
    # intra-host interconnect bandwidth (bytes/s per ICI link): what a
    # tensor-parallel all-reduce inside this node's mesh slice pays per
    # hop.  0 = unknown — the solver then neither merges this device into
    # a mesh slice nor charges TP collective cost (today's behavior).
    ici_bw: float = 0.0

    def ici_adjacent(self, other: "DeviceInfo") -> bool:
        """ICI adjacency = same host and same slice (the reference's
        Thunderbolt-link analog, src/dnet/api/cluster.py:52)."""
        return self.host == other.host and self.slice_id == other.slice_id


@dataclass
class LayerAssignment:
    """One device's share of the ring.

    layers: flattened absolute layer ids over all k rounds (contiguous per
    round).  window_size / residency_size drive the weight-streaming policy.
    Reference: src/dnet/core/types/topology.py:14-28.
    """

    instance: str
    layers: List[int]
    rounds: List[List[int]] = field(default_factory=list)
    next_instance: str = ""
    window_size: int = 0
    residency_size: int = 0
    # host-local mesh under this ring node (parallel/shard_mesh.py): the
    # window runs tensor/sequence-parallel over the shard's local chips.
    # 0 = the shard's own DNET_SHARD_MESH_* default; 1 = single chip.
    mesh_tp: int = 0
    mesh_sp: int = 0
    # NamedSharding tensor parallelism (parallel/tp.py): set by the
    # solver's mesh-slice placement for pure-TP shards (no sp, resident
    # weights); rides the load body into shard/compute.py.  0 = unset
    # (the shard's DNET_TP default decides), 1 = pinned single-chip.
    tp_degree: int = 0

    @property
    def min_layer(self) -> int:
        return min(self.layers) if self.layers else -1


@dataclass
class TopologyInfo:
    """Solver output: the full ring plan shared API <-> shards.

    Reference: src/dnet/core/types/topology.py:30-49.
    """

    model: str
    num_layers: int
    kv_bits: int
    devices: List[DeviceInfo]
    assignments: List[LayerAssignment]
    solution: dict = field(default_factory=dict)  # solver diagnostics (k, w, n, obj)
    # membership epoch minted when the API installed this topology
    # (dnet_tpu/membership/epoch.py); 0 = never installed (manual tests)
    epoch: int = 0

    def assignment_for(self, instance: str) -> Optional[LayerAssignment]:
        for a in self.assignments:
            if a.instance == instance:
                return a
        return None

    def head_instance(self) -> str:
        """Owner of layer 0 (first hop target for token injection)."""
        for a in self.assignments:
            if 0 in a.layers:
                return a.instance
        raise ValueError("no assignment owns layer 0")

    def tail_instance(self) -> str:
        last = self.num_layers - 1
        for a in self.assignments:
            if last in a.layers:
                return a.instance
        raise ValueError("no assignment owns the last layer")
