"""Central configuration.

Layered precedence (low to high): built-in defaults -> `.env` file -> process
environment -> CLI overrides.  Mirrors the reference's ten `DNET_*`
pydantic-settings groups (reference: src/dnet/config.py:23-263) with a
dependency-free dataclass implementation (pydantic-settings is not available
in this image) plus TPU-specific groups (mesh/ICI).

Every field of every group is settable as ``<PREFIX><UPPER_NAME>`` in the
environment, e.g. ``DNET_GRPC_MAX_MESSAGE_MB=128``.

THIS MODULE IS THE ONLY SANCTIONED READER OF ``DNET_*`` ENVIRONMENT
VARIABLES (static-analysis check DL006, ``scripts/dnetlint.py``): a raw
``os.environ.get("DNET_...")`` elsewhere silently skips .env layering,
type casting, and ``.env.example`` generation.  Consumers use a
``Settings`` field; the handful of flags that must observe env flips
AFTER the settings cache warmed (test toggles, operator kill-switches)
go through :func:`env_flag` below.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional, Type, TypeVar

T = TypeVar("T", bound="_EnvGroup")

_TRUTHY = {"1", "true", "yes", "on"}
_FALSY = {"0", "false", "no", "off", ""}


def _parse_bool(raw: str) -> bool:
    low = raw.strip().lower()
    if low in _TRUTHY:
        return True
    if low in _FALSY:
        return False
    raise ValueError(f"not a boolean: {raw!r}")


def _cast(raw: str, typ: Any) -> Any:
    # Optional[X] -> X for casting; "none"/"" selects None.
    import typing

    origin = typing.get_origin(typ)
    if origin is typing.Union:
        args = [a for a in typing.get_args(typ) if a is not type(None)]
        if raw.strip().lower() in {"none", "null", ""}:
            return None
        typ = args[0]
    if typ is bool:
        return _parse_bool(raw)
    if typ is int:
        return int(raw)
    if typ is float:
        return float(raw)
    if typ is Path:
        return Path(raw).expanduser()
    if typ is str:
        return raw
    if typing.get_origin(typ) is list or typ is list:
        return [s.strip() for s in raw.split(",") if s.strip()]
    return raw


@functools.lru_cache(maxsize=8)
def _load_dotenv_cached(path: str, mtime: float) -> dict[str, str]:
    result: dict[str, str] = {}
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if not line or line.startswith("#") or "=" not in line:
            continue
        key, _, value = line.partition("=")
        result[key.strip()] = value.strip().strip("'\"")
    return result


def load_dotenv(path: str | Path = ".env") -> dict[str, str]:
    """Parse a KEY=VALUE .env file (comments and blank lines ignored).

    Cached by (path, mtime) so the ten settings groups constructed by
    ``Settings()`` share one read.
    """
    p = Path(path)
    try:
        mtime = p.stat().st_mtime
    except OSError:
        return {}
    return _load_dotenv_cached(str(p), mtime)


class _EnvGroup:
    """Mixin: populate dataclass fields from `<env_prefix><FIELD>` vars."""

    env_prefix: str = "DNET_"

    @classmethod
    def from_env(cls: Type[T], env: Optional[dict[str, str]] = None) -> T:
        source: dict[str, str] = {}
        source.update(load_dotenv(os.environ.get("DNET_ENV_FILE", ".env")))
        source.update(os.environ)
        if env:
            source.update(env)
        kwargs: dict[str, Any] = {}
        for f in dataclasses.fields(cls):  # type: ignore[arg-type]
            key = f"{cls.env_prefix}{f.name.upper()}"
            if key in source:
                try:
                    kwargs[f.name] = _cast(source[key], cls.type_hint(f))  # type: ignore[attr-defined]
                except (ValueError, TypeError) as exc:
                    raise ValueError(f"bad value for {key}: {exc}") from exc
        return cls(**kwargs)  # type: ignore[call-arg]


# dataclasses stores string annotations under `from __future__ import
# annotations`; resolve them once per class.
def _resolve_hints(cls: type) -> None:
    import typing

    hints = typing.get_type_hints(cls)

    def type_hint(f: dataclasses.Field) -> Any:
        return hints[f.name]

    cls.type_hint = staticmethod(type_hint)  # type: ignore[attr-defined]


def _default_log_dir() -> Path:
    try:
        return Path("~/.dnet-tpu/logs").expanduser()
    except RuntimeError:  # no resolvable home dir (bare container uid)
        return Path("/tmp/dnet-tpu-logs")


@dataclass
class LogSettings(_EnvGroup):
    env_prefix = "DNET_LOG_"
    level: str = "INFO"
    dir: Path = field(default_factory=_default_log_dir)
    to_file: bool = True


@dataclass
class ObsSettings(_EnvGroup):
    """Observability: [PROFILE] log gating and device-sync knobs.

    Reference: src/dnet/core/observability.py:31-83.
    """

    env_prefix = "DNET_OBS_"
    enabled: bool = False
    sync_per_layer: bool = False
    sync_every_n: int = 0
    # SLO targets over a rolling window (obs/slo.py): 0 disables a target.
    # Burning SLOs flip /health to "degraded" and export dnet_slo_* gauges.
    slo_window_s: float = 300.0
    slo_ttft_p95_ms: float = 0.0
    slo_decode_p95_ms: float = 0.0
    slo_availability: float = 0.0  # e.g. 0.999; fraction of requests OK
    # /v1/cluster/metrics + cluster timeline: per-shard HTTP fetch timeout
    cluster_scrape_timeout_s: float = 5.0
    # flight-recorder sampling under load: record every Nth request's full
    # span timeline (summary spans — ttft, the closing request span — are
    # recorded for EVERY request regardless).  1 = record everything; N > 1
    # keeps a load run from thrashing the bounded timeline ring.
    trace_sample: int = 1
    # Perfetto trace export (obs/trace.py, GET /v1/debug/trace):
    # serving-window dump default horizon and a hard cap on emitted trace
    # events (oldest timelines dropped first past the cap)
    trace_window_s: float = 120.0
    trace_max_events: int = 50000
    # scheduler tick flight-recorder ring capacity (sched/flight.py,
    # GET /v1/debug/sched); 0 disables capture entirely
    tick_records: int = 256
    # structured wide-event journal (obs/events.py, GET /v1/debug/events):
    # bounded in-memory ring capacity (oldest evicted past it, counted as
    # dropped) and an optional JSONL file sink ("" disables the file)
    events_records: int = 2048
    events_path: str = ""

    def sync_stride(self) -> int:
        """Normalized decode-step sync cadence: 0 = never fence, N >= 1 =
        fence every N steps (1 = every step).  THE place owning the 0-vs-1
        semantics — call sites must use this, not the raw field (negative
        values clamp to never)."""
        return max(int(self.sync_every_n), 0)


@dataclass
class KVSettings(_EnvGroup):
    """KV-cache defaults (bits=0 means unquantized bf16)."""

    env_prefix = "DNET_KV_"
    bits: int = 0
    group_size: int = 64
    max_seq_len: int = 4096
    ttl_seconds: float = 600.0
    # the paged pool (dnet_tpu/kv/), which the batched engine attends in
    # place wherever the model and the cache allow it (core/batch.py:
    # kv_layout): tokens per KV block (the allocation granule); must
    # divide max_seq
    block_tokens: int = 16
    # total pool capacity in blocks; 0 = auto-size to the engine's dense
    # equivalent (slots x max_seq / block_tokens)
    pool_blocks: int = 0


@dataclass
class ComputeSettings(_EnvGroup):
    env_prefix = "DNET_COMPUTE_"
    wire_dtype: str = "bfloat16"  # activations on the wire (bf16 is TPU-native)
    compute_dtype: str = "bfloat16"
    window_size: int = 0  # 0 = all assigned layers in one window
    residency_windows: int = 2
    donate_activations: bool = True
    # MoE compute path: auto | dense | grouped | dispatch | a2a (ops/moe.py).
    # auto (the default) is exact and decided from static shapes: on one
    # rank, a program with more rows than the ridge (RIDGE_ROWS, 256), or
    # one whose rows are expected to choose at most SPARSE_SHARE of the held
    # experts (a decode step over a share of many experts; not under a tp
    # axis or a vmap over lanes, not with quantized experts), computes its
    # routed experts by a no-drop grouped matmul over rows sorted by expert,
    # any other program by the dense einsum (on several ranks always).
    # dispatch and a2a may DROP over-capacity tokens (GShard semantics), a
    # throughput trade the operator opts into by name.
    moe_impl: str = "auto"
    # per-expert capacity = ceil(k * n_tokens * factor / n_experts);
    # <= 0 selects the exact no-drop capacity (C = n_tokens)
    moe_capacity_factor: float = 1.25


@dataclass
class TransportSettings(_EnvGroup):
    env_prefix = "DNET_TRANSPORT_"
    compress: bool = False
    compress_pct: float = 0.5
    compress_quant_bits: int = 0
    send_retries: int = 3
    stream_idle_sweep_s: float = 30.0
    stream_backoff_s: float = 0.25


@dataclass
class WireSettings(_EnvGroup):
    """Overlapped quantized wire pipeline (transport/wire_pipeline.py).

    ``DNET_WIRE_PIPELINE=1`` takes the hop codec off the serial send path:
    the shard compute thread only LAUNCHES the on-device encode (jitted
    quant/sparsify with a donated activation buffer) and hands the pending
    device buffers to the transport tx stage, which finishes the D2H
    readback + byte packing off-thread while the next frame computes; the
    receive side symmetrically launches H2D upload + on-device dequant at
    ingress so the dequant of frame N+1 overlaps frame N's compute.  A
    bounded ``DEPTH``-slot ring of encode buffers provides backpressure.
    ``CODEC`` picks the hop codec: ``auto`` (the default — the ring
    manager resolves per hop: lossy ``qsparse8`` for hops that CROSS
    hosts, ``lossless`` for same-host/loopback hops and single-shard
    rings, so greedy SSE streams stay byte-identical wherever no DCN is
    paid), ``lossless`` (wire-dtype cast, exact, everywhere), or
    ``qsparse8`` (int8-affine kept columns, ~4x fewer bytes, lossy,
    everywhere).
    The gate is also honored as a raw env flip via
    ``env_flag("DNET_WIRE_PIPELINE")`` so post-cache toggles (tests,
    operators) still see it.
    """

    env_prefix = "DNET_WIRE_"
    # master switch: double-buffered encode/decode overlap on shard hops
    pipeline: bool = False
    # hop codec default: auto | lossless | qsparse8 (auto = inter-host
    # hops ride qsparse8, same-host/loopback hops stay lossless)
    codec: str = "auto"
    # column drop fraction the qsparse8 hop codec uses when transport
    # compression is not separately configured
    qsparse_pct: float = 0.5
    # int8 quant group along kept columns; frames with fewer kept columns
    # than one group fall back to per-tensor fp32 scales (gs=0 tag)
    group_size: int = 64
    # encode-buffer ring depth: how many launched-but-unsent frames the
    # compute thread may run ahead of the tx readback (backpressure bound)
    depth: int = 2


@dataclass
class ResilienceSettings(_EnvGroup):
    """Request survival: retry/backoff policy + transparent decode resume.

    `resume=1` turns a mid-decode shard failure from a surfaced 503 into a
    checkpoint -> wait-for-recovery -> replay-prefill cycle on the SAME
    client stream (dnet_tpu/resilience/checkpoint.py).  The retry knobs
    scale the default unary-RPC backoff policy (resilience/policy.py);
    per-RPC-class overrides stay in code.
    """

    env_prefix = "DNET_RESILIENCE_"
    # transparent decode resume across shard failure (InferenceManager)
    resume: bool = False
    # per-resume budget for the ring to become healthy again before the
    # original error is surfaced to the client
    resume_deadline_s: float = 30.0
    # resume attempts per request; past this the failure surfaces
    max_resumes: int = 2
    # default unary-RPC retry policy (exponential backoff + full jitter)
    retry_attempts: int = 3
    retry_base_s: float = 0.05
    retry_max_s: float = 2.0
    # 0 = nondeterministic jitter; nonzero seeds the jitter RNG (tests)
    retry_jitter_seed: int = 0


@dataclass
class AdmissionSettings(_EnvGroup):
    """Overload survival (dnet_tpu/admission/): bounded admission, load
    shedding, end-to-end deadlines, graceful drain.

    The wait queue holds at most ``ADMIT_QUEUE_DEPTH`` requests beyond the
    executing set (``DNET_API_MAX_CONCURRENT_REQUESTS``); the rest shed
    immediately with 429 + ``Retry-After`` derived from the observed
    service rate.  ``REQUEST_DEADLINE_S`` (per-request ``deadline_s``
    overrides it) rides activation frame headers so shards drop expired
    frames at dequeue.  On SIGTERM the server drains: 503 for new work,
    in-flight requests bounded by ``DRAIN_DEADLINE_S``.
    """

    env_prefix = "DNET_"
    # waiting requests beyond the executing set; 0 = shed everything that
    # cannot start immediately
    admit_queue_depth: int = 32
    # longest a request may wait for a slot before shedding with 429
    admit_queue_timeout_s: float = 10.0
    # default end-to-end deadline; 0 disables (per-request `deadline_s`
    # still applies when set)
    request_deadline_s: float = 0.0
    # how long SIGTERM waits for in-flight requests before tearing down
    drain_deadline_s: float = 30.0


@dataclass
class LoadgenSettings(_EnvGroup):
    """Serving-grade load generation (dnet_tpu/loadgen/): an OPEN-LOOP
    arrival process (requests fire on schedule, never gated on completions)
    of N concurrent OpenAI-API streaming clients with a seeded mixed
    prompt/output-length workload.  `bench_serve.py` drives it and emits a
    machine-readable ``BENCH_SERVE_*.json`` report (goodput over completed
    requests only, TTFT/TPOT/E2E tail percentiles, shed-rate breakdown,
    SLO cross-validation, decode-phase and JIT-compile summaries).
    """

    env_prefix = "DNET_LOADGEN_"
    # workload schedule: a pure function of (seed, requests, rate, buckets)
    seed: int = 0
    requests: int = 64
    # mean arrival rate; poisson draws exponential inter-arrivals, fixed
    # spaces arrivals exactly 1/rate apart
    rate_rps: float = 8.0
    arrival: str = "poisson"  # poisson | fixed
    # mixed length classes "prompt:max_tokens,..." (tokens are exact for
    # byte-level tokenizers, approximate for BPE)
    buckets: str = "8:16,32:8,64:4"
    # optional comma floats weighting the buckets (default: uniform)
    weights: str = ""
    temperature: float = 0.0
    # report measurement starts here: requests SCHEDULED before warmup_s
    # still run (they warm compiles/caches) but are excluded from goodput
    # and percentiles
    warmup_s: float = 0.0
    # per-request client-side budget (stream must finish within this)
    timeout_s: float = 120.0


@dataclass
class MembershipSettings(_EnvGroup):
    """Elastic ring membership (dnet_tpu/membership/): topology epochs,
    quarantine, and automatic shard rejoin.

    With auto-recovery on, a permanently lost shard is fenced out by an
    epoch-bumping re-solve and moves to a QUARANTINE list that keeps
    health-probing it.  ``DNET_REJOIN=1`` lets a quarantined shard that
    probes green for ``REJOIN_STABLE_S`` seconds trigger a re-profile +
    re-solve through the delta-reload path, restoring full capacity with
    no operator action.  ``RECOVERY_MAX_ROUNDS`` bounds the convergence
    loop when further shards die during an in-flight recovery.
    """

    env_prefix = "DNET_"
    # automatic rejoin of quarantined shards that probe healthy again
    rejoin: bool = False
    # consecutive-green seconds before a quarantined shard may rejoin
    rejoin_stable_s: float = 15.0
    # recovery convergence: max re-solve rounds per failure burst (each
    # round re-checks down_shards() after its reload)
    recovery_max_rounds: int = 3


@dataclass
class SchedSettings(_EnvGroup):
    """Iteration-level continuous-batching scheduler (dnet_tpu/sched/),
    the serving engine of every local model load the batched engine can
    take (api/model_manager.py: serving_plan): every tick packs up to
    ``SCHED_TOKEN_BUDGET`` tokens of chunked-prefill segments plus one
    decode step per running sequence into one batch plan, admits new work
    only when the paged-KV block pool can cover it, and preempts the
    lowest-priority sequence back to WAITING (paged prefix kept) under
    block starvation.
    """

    env_prefix = "DNET_"
    # per-tick token budget shared by chunked-prefill segments (1 token
    # each) and decode steps (1 per running sequence)
    sched_token_budget: int = 2048
    # largest chunked-prefill segment per request per tick; 0 = the token
    # budget (a prompt's chunk is as wide as the budget holds: one pass
    # over the weights for it, not one every 256 tokens)
    sched_prefill_chunk: int = 0
    # batch lanes the scheduler engine allocates; 0 = max(batch_slots, 8)
    sched_slots: int = 0

    def prefill_chunk_cap(self) -> int:
        """The resolved cap: what the scheduler hands one request a tick at
        most, and what the window kind's pool is sized to take."""
        return int(self.sched_prefill_chunk) or int(self.sched_token_budget)


@dataclass
class FleetSettings(_EnvGroup):
    """Fleet routing (dnet_tpu/fleet/): N ring replicas behind one
    prefix-affine, least-loaded front door.

    ``DNET_FLEET=N`` (N > 1) puts the FleetManager in front of
    /v1/chat/completions: requests route prefix-affinity-first (sticking
    a conversation to the replica holding its COW prefix blocks), then
    least-loaded by live admission occupancy; a replica that dies
    mid-stream fails over to a survivor via deterministic replay.  The
    default 1 keeps today's single-ring serve path byte-identical — the
    fleet layer is never constructed.
    """

    env_prefix = "DNET_"
    # replica count the front door expects; 1 = no fleet layer at all
    fleet: int = 1
    # bounded LRU affinity table: conversations tracked before the
    # coldest sticky entry is evicted
    fleet_affinity_capacity: int = 512
    # leading prefix units (text chars) hashed into the affinity key
    fleet_affinity_prefix: int = 256
    # migrate in-flight streams off a dead replica via replay; off =
    # a mid-stream death surfaces as an in-band stream error instead
    fleet_failover: bool = True


@dataclass
class SanSettings(_EnvGroup):
    """Runtime concurrency sanitizer (dnet_tpu/analysis/runtime/, "dsan").

    ``DNET_SAN=1`` arms the suite: the event-loop stall watchdog,
    ownership-domain guards on the declared shared structures,
    lock-acquisition-order tracking, and the task-leak audit.  Findings
    (DS001-DS006) reuse the dnetlint Finding model and merge into the
    ``ANALYSIS_r<NN>.json`` records.  Off (the default), every hook is a
    no-op — nothing is wrapped, zero cost on the serving path.  The gate
    is read via ``config.env_flag`` so post-cache env flips (the pytest
    fixtures) still arm it.
    """

    env_prefix = "DNET_"
    # master switch; also honored as a raw env flip via env_flag("DNET_SAN")
    san: bool = False
    # loop blocked longer than this is a DS001 stall finding
    san_stall_ms: float = 250.0
    # watchdog sampling cadence; 0 = stall_ms / 4
    san_poll_ms: float = 0.0
    # where sanitized runs persist findings for the dnetlint merge;
    # "" = <repo>/.dsan-findings.json
    san_report: str = ""


@dataclass
class ChaosSettings(_EnvGroup):
    """Deterministic fault injection (dnet_tpu/resilience/chaos.py).

    ``DNET_CHAOS="shard_compute:error_at:5,send_activation:error:0.1,
    token_cb:delay:50ms"`` — comma-separated ``point:kind:param`` specs over
    the named injection points; the schedule is a pure function of
    ``DNET_CHAOS_SEED`` and the per-point call counters, so a failing run
    replays exactly.
    """

    env_prefix = "DNET_"
    chaos: str = ""
    chaos_seed: int = 0


@dataclass
class TpSettings(_EnvGroup):
    """Intra-shard tensor parallelism (parallel/tp.py, parallel/
    tp_collectives.py).

    ``DNET_TP=N`` makes a ring shard run its layer window tensor-parallel
    over N host-local chips on a ("batch", "model") NamedSharding mesh:
    weights load pre-sharded (per-chip slices, never a full tensor on one
    chip), the KV cache shards on the head axis, and each layer pays two
    collectives — attention out-proj and MLP down-proj all-reduces —
    routed through the quantizable seam.  ``TP_COLLECTIVE`` picks their
    wire format: ``lossless`` (exact psum — greedy SSE byte-identical to
    tp=1), ``q8`` (EQuARX-style grouped-int8: 1-byte codes + per-group
    scale/bias instead of 2-4 byte floats), or ``auto`` (q8 on real
    accelerator meshes, lossless on CPU).  A solver-placed topology
    overrides the env default per shard via the load body's
    ``tp_degree``.  1 = off, today's single-chip behavior.
    """

    env_prefix = "DNET_"
    # tensor-parallel degree for shards loaded without an explicit
    # tp_degree (1 = off); must divide the model's attention/KV head counts
    tp: int = 1
    # collective wire format: auto | lossless | q8
    tp_collective: str = "auto"
    # int8 quant group along the flattened activation for q8 collectives
    tp_group_size: int = 64


@dataclass
class GrpcSettings(_EnvGroup):
    """gRPC channel tuning (reference: src/dnet/utils/grpc_config.py:29-53)."""

    env_prefix = "DNET_GRPC_"
    max_message_mb: int = 64
    max_concurrent_streams: int = 1024
    keepalive_time_ms: int = 20000
    keepalive_timeout_ms: int = 10000
    http2_bdp_probe: bool = False


@dataclass
class ApiSettings(_EnvGroup):
    env_prefix = "DNET_API_"
    host: str = "0.0.0.0"
    http_port: int = 8080
    grpc_port: int = 58080
    callback_addr: str = ""  # override for non-loopback token callback
    request_timeout_s: float = 300.0
    max_concurrent_requests: int = 8
    max_batch_size: int = 8
    models_dir: str = "~/.dnet-tpu/models"
    max_seq_len: int = 4096
    param_dtype: str = "bfloat16"
    health_interval_s: float = 5.0
    health_fail_threshold: int = 3
    # 0 = serve weights in param_dtype; 8 = int8, 4 = packed-int4 weight-only
    # quantization (per-group symmetric, ops/quant.py) — ~2x / ~4x decode
    # roofline on HBM-bound batch-1 serving
    weight_quant_bits: int = 0
    # quantization group size along the contraction dim (0 = quantizer
    # default: 128 for int8, 64 for int4).  Tensor-parallel serving needs a
    # value dividing in/tp for every quantized weight.
    weight_quant_group: int = 0
    # >1 = continuous batching: that many KV slots share one vmapped decode
    # program (core/batch.py); concurrent requests coalesce per step
    batch_slots: int = 1
    # >0 = cache that many full-prompt KV snapshots; a request whose prompt
    # EXTENDS a cached prompt (multi-turn chat resending its history)
    # prefills only the new suffix (core/prefix_cache.py).  Exact-prefix
    # match; each snapshot is a full KV alloc.  Local/batched engines only.
    prefix_cache: int = 0
    # >0 = prompt-lookup speculative decoding: draft that many tokens per
    # verify forward (core/spec.py).  Greedy-exact; eligible requests emit
    # 1..L+1 tokens per weight read.  Local and mesh engines (batch 1).
    spec_lookahead: int = 0
    # draft-MODEL speculation (single-process serving, LocalEngine only):
    # a smaller same-vocab checkpoint drafts SPEC_LOOKAHEAD tokens per
    # verify block instead of prompt-lookup — better acceptance on
    # non-repetitive text.  Checkpoint path or models_dir id; "" = off.
    draft_model: str = ""
    # ring decode grants: a token frame may authorize the TAIL shard to
    # feed up to this many sampled tokens straight back into the ring
    # (tail -> head hop), removing the per-token API round trip.  The tail
    # halts on EOS / cache capacity; overshoot past a stop SEQUENCE is
    # discarded like local decode chunks.  0 disables.
    ring_auto_steps: int = 16
    # compile the decode-chunk program matrix at LOAD time (no first-request
    # ramp stall).  0 defers every compile to first use — faster model
    # hot-swaps where startup latency matters more than first-token latency
    # (CI model-matrix loops, A/B harnesses).
    warm_on_load: bool = True
    # batched lanes over the ring: >1 coalesces that many concurrent
    # requests' decode steps into ONE multi-lane ring pass (shard/lanes.py).
    # Needs a single-round resident-weight topology; composes with
    # mesh-backed shards.  Grants and ring speculation are per-nonce
    # self-pacing and turn off when lanes are on.  0/1 = off.
    ring_lanes: int = 0


@dataclass
class ShardSettings(_EnvGroup):
    env_prefix = "DNET_SHARD_"
    host: str = "0.0.0.0"
    http_port: int = 8081
    grpc_port: int = 58081
    queue_size: int = 256
    name: str = ""
    models_dir: str = "~/.dnet-tpu/models"
    # per-layer repack cache for weight streaming (reference repack.py)
    repack_dir: str = "~/.dnet-tpu/repacked"
    # host-local mesh under this shard's ring node: the layer window runs
    # tensor-parallel (tp) / sequence-parallel (sp) across the host's ICI
    # chips while ring hops stay gRPC/DCN (parallel/shard_mesh.py).
    # tp=1/sp=1 = single-device; tp=-1 = every local device on the tp axis.
    # A /load_model request with explicit mesh fields overrides these.
    mesh_tp: int = 1
    mesh_sp: int = 1


@dataclass
class TopologySettings(_EnvGroup):
    env_prefix = "DNET_TOPOLOGY_"
    solver: str = "auto"  # auto | greedy | milp
    mip_gap: float = 1e-4
    seq_len: int = 4096


@dataclass
class MeshSettings(_EnvGroup):
    """TPU mesh axes used by the in-slice single-program ring / TP / SP."""

    env_prefix = "DNET_MESH_"
    pp: int = 0  # 0 = infer from device count
    tp: int = 1
    dp: int = 1
    sp: int = 1
    # multi-host pods: when set, jax.distributed.initialize() runs before
    # the first backend use so jax.devices() spans every host of the slice
    # and the mesh engines build over the GLOBAL device set (DCN-connected
    # slices included) — the TPU analog of the reference's NCCL/MPI-style
    # multi-node backend.  Format "host:port" of process 0.
    coordinator: str = ""
    num_processes: int = 0  # 0 = single-process (no distributed init)
    process_id: int = 0


@dataclass
class Settings:
    log: LogSettings = field(default_factory=LogSettings.from_env)
    obs: ObsSettings = field(default_factory=ObsSettings.from_env)
    kv: KVSettings = field(default_factory=KVSettings.from_env)
    compute: ComputeSettings = field(default_factory=ComputeSettings.from_env)
    transport: TransportSettings = field(default_factory=TransportSettings.from_env)
    wire: WireSettings = field(default_factory=WireSettings.from_env)
    resilience: ResilienceSettings = field(default_factory=ResilienceSettings.from_env)
    admission: AdmissionSettings = field(default_factory=AdmissionSettings.from_env)
    loadgen: LoadgenSettings = field(default_factory=LoadgenSettings.from_env)
    membership: MembershipSettings = field(default_factory=MembershipSettings.from_env)
    sched: SchedSettings = field(default_factory=SchedSettings.from_env)
    fleet: FleetSettings = field(default_factory=FleetSettings.from_env)
    san: SanSettings = field(default_factory=SanSettings.from_env)
    tp: TpSettings = field(default_factory=TpSettings.from_env)
    chaos: ChaosSettings = field(default_factory=ChaosSettings.from_env)
    grpc: GrpcSettings = field(default_factory=GrpcSettings.from_env)
    api: ApiSettings = field(default_factory=ApiSettings.from_env)
    shard: ShardSettings = field(default_factory=ShardSettings.from_env)
    topology: TopologySettings = field(default_factory=TopologySettings.from_env)
    mesh: MeshSettings = field(default_factory=MeshSettings.from_env)


for _cls in (
    LogSettings,
    ObsSettings,
    KVSettings,
    ComputeSettings,
    TransportSettings,
    WireSettings,
    ResilienceSettings,
    AdmissionSettings,
    LoadgenSettings,
    MembershipSettings,
    SchedSettings,
    FleetSettings,
    SanSettings,
    TpSettings,
    ChaosSettings,
    GrpcSettings,
    ApiSettings,
    ShardSettings,
    TopologySettings,
    MeshSettings,
):
    _resolve_hints(_cls)


def env_flag(name: str, default: bool = False) -> bool:
    """Sanctioned RAW process-env boolean read — the documented DL006
    escape hatch for flags that must see ``os.environ`` flips after the
    ``get_settings()`` cache warmed: the ``DNET_PROFILE`` test toggle
    and the ``DNET_FLASH_DECODE`` /
    ``DNET_FLASH_INTERPRET`` operator kill-switches.  Unset,
    set-but-empty (``DNET_X=``, the shell/compose idiom for "unset"),
    or unparseable values return ``default`` — an empty string must not
    silently disable a default-enabled kill-switch.  Everything else
    goes through a ``Settings`` field."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        return _parse_bool(raw)
    except ValueError:
        return default


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Called first thing by every entry point (the three CLIs and the two
    benchmarks), so a server start finds the programs the previous start
    compiled.  ``JAX_COMPILATION_CACHE_DIR`` wins and nothing is touched
    (JAX reads the variable itself); otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` — never a temp name, because the directory is
    part of what a later process must find again.  No other code sets the
    option."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = str(Path(__file__).resolve().parent.parent / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@functools.lru_cache(maxsize=1)
def get_settings() -> Settings:
    return Settings()


def reset_settings_cache() -> None:
    """For tests that mutate the environment."""
    get_settings.cache_clear()
