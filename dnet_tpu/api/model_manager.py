"""Model lifecycle on the API node.

Single-process mode: builds the engine `serving_plan` names + tokenizer in
an executor.
Ring mode (two-role split) extends this with per-shard /load_model fan-out
(reference: src/dnet/api/model_manager.py:54-255).

Model resolution is local-only (zero-egress environments are first-class):
a model id is either a filesystem path or a subdirectory of
`DNET_API_MODELS_DIR` (repo id slashes replaced by `--`, HF-cache style).
"""

from __future__ import annotations

import asyncio
import time
from pathlib import Path
from typing import NamedTuple, Optional

from dnet_tpu.config import get_settings
from dnet_tpu.utils.logger import get_logger
from dnet_tpu.utils.tokenizer import load_tokenizer

log = get_logger()


def resolve_model_dir(model_id: str, models_dir: Optional[str | Path] = None) -> Optional[Path]:
    p = Path(model_id).expanduser()
    if p.is_dir() and (p / "config.json").is_file():
        return p
    if models_dir:
        base = Path(models_dir).expanduser()
        for cand in (
            base / model_id,
            base / model_id.replace("/", "--"),
            base / model_id.split("/")[-1],
        ):
            if cand.is_dir() and (cand / "config.json").is_file():
                return cand
    return None


class ServingPlan(NamedTuple):
    """What serves a load: class names of the engine and the adapter, the
    KV layout (core/batch.py KV_PAGED / KV_DENSE / KV_STATE; "mesh" = sharded
    by the mesh engine) and the one-line reason (the load's log line, /health)."""

    engine: str
    adapter: str
    kv: str
    reason: str


def serving_plan(
    model,
    *,
    mesh: Optional[dict],
    batch_slots: int,
    streams_weights: bool,
    kv_quant_bits: int,
    spec_lookahead: int,
    draft_dir,
    max_seq: int,
    n_devices: int = 0,
) -> ServingPlan:
    """THE decision of what serves a loaded model, from what the code can
    see; no setting selects a path.  `model` is the ring model built from
    the checkpoint's config (no weights needed).  In order, each rule a
    case the path below it cannot run:

    1. a mesh is asked for -> the mesh engines: the staggered pipeline
       where `batch_slots` fill it, else the sequential mesh;
    2. weights stream from disk, the model has no gated KV writes, or a
       draft MODEL speculates -> LocalEngine, one sequence at a time;
    3. otherwise the scheduler over BatchedEngine's lanes;
    4. its KV cache by core/batch.py: kv_layout — a state entry a lane for
       a model whose layers keep a recurrent state; else the paged pool
       attended in place unless the kernel, the pool or speculation
       refuses."""
    if mesh is not None:
        why = _pipeline_refusal(model, mesh, batch_slots, n_devices)
        if why is None:
            return ServingPlan(
                "PipelinedMeshEngine", "BatchedLocalAdapter", "mesh",
                f"mesh {mesh}: {batch_slots} slots fill the pipeline",
            )
        return ServingPlan(
            "MeshEngine", "LocalAdapter", "mesh",
            f"mesh {mesh}, one sequence at a time: {why}",
        )
    from dnet_tpu.core.batch import KV_DENSE, kv_layout

    why = None
    if streams_weights:
        why = "weights stream from disk"
    elif not model.supports_kv_commit:
        why = f"{model.config.model_type} has no gated KV writes"
    elif draft_dir is not None and spec_lookahead > 0:
        why = "a draft model speculates"
    if why is not None:
        return ServingPlan(
            "LocalEngine", "LocalAdapter", KV_DENSE, f"one sequence at a time: {why}"
        )
    kv, why = kv_layout(model, kv_quant_bits, spec_lookahead, max_seq)
    return ServingPlan("BatchedEngine", "SchedulerAdapter", kv, f"{kv} KV: {why}")


def _pipeline_refusal(
    model, mesh: dict, batch_slots: int, n_devices: int
) -> Optional[str]:
    """Why the staggered-microbatch pipeline cannot serve this mesh request
    (None = it can): checked before the build so that an incompatible
    config degrades to the sequential mesh instead of failing load_model."""
    dp = mesh.get("dp", 1)
    if batch_slots <= 1:
        return "batch_slots is 1"
    if batch_slots % dp:
        return (
            f"batch_slots={batch_slots} not divisible by dp={dp} "
            "(pipelined batching needs whole lanes)"
        )
    if not model.supports_kv_commit:
        return f"pipelined batching unsupported for {model.config.model_type}"
    pp = mesh.get("pp", 0)
    if pp <= 0:
        from dnet_tpu.parallel.pipelined import resolve_pp

        pp = resolve_pp(
            n_devices, mesh.get("tp", 1) * dp, mesh.get("sp", 1),
            model.config.num_hidden_layers,
        )
    if batch_slots // dp < pp:
        return (
            f"batch_slots={batch_slots} gives {batch_slots // dp} slots per "
            f"dp lane, < pp={pp}: cannot fill the pipeline (raise batch_slots)"
        )
    return None


class LocalModelManager:
    """Owns the engine + tokenizer for single-process serving."""

    def __init__(
        self,
        inference_manager,
        models_dir: Optional[str] = None,
        max_seq: int = 4096,
        param_dtype: str = "bfloat16",
        mesh: Optional[dict] = None,  # {"pp","tp","dp","sp"} -> MeshEngine
        weight_quant_bits: int = 0,
        weight_quant_group: int = 0,
        kv_bits: int = 0,
        batch_slots: int = 1,
        prefix_cache: int = 0,
        spec_lookahead: int = 0,
    ) -> None:
        self.inference = inference_manager
        self.models_dir = models_dir
        self.max_seq = max_seq
        self.param_dtype = param_dtype
        self.weight_quant_bits = weight_quant_bits
        self.weight_quant_group = weight_quant_group
        self.kv_bits = kv_bits
        self.batch_slots = batch_slots
        self.prefix_cache = prefix_cache
        self.spec_lookahead = spec_lookahead
        # active when any axis is parallel or pp is left to infer (pp=0 with
        # another axis set, or an explicit pp)
        self.mesh = mesh if mesh and (any(v > 1 for v in mesh.values()) or mesh.get("pp", 0) > 1) else None
        self.engine = None
        self.model_dir: Optional[Path] = None
        #: the loaded model's ServingPlan (/health shows it)
        self.serving: Optional[ServingPlan] = None

    @property
    def current_model_id(self) -> Optional[str]:
        return self.inference.model_id

    def is_model_available(self, model_id: str) -> bool:
        from dnet_tpu.api.catalog import split_variant

        return resolve_model_dir(split_variant(model_id)[0], self.models_dir) is not None

    async def load_model(self, model_id: str, max_seq: Optional[int] = None) -> float:
        """Returns load time in seconds; raises on failure.

        `<id>:int8` / `<id>:int4` quant-variant aliases (catalog rows the
        reference enumerates per model, src/dnet/api/catalog.py:4-175) load
        the BASE checkpoint with weight-only quantization overridden."""
        from dnet_tpu.api.catalog import split_variant

        base_id, variant_bits = split_variant(model_id)
        model_dir = resolve_model_dir(base_id, self.models_dir)
        if model_dir is None:
            raise FileNotFoundError(
                f"model {model_id!r} not found locally (models_dir={self.models_dir})"
            )
        wq_bits = self.weight_quant_bits if variant_bits is None else variant_bits
        wq_group = self.weight_quant_group
        if variant_bits:
            from dnet_tpu.ops.quant import DEFAULT_GROUP, DEFAULT_GROUP_Q4

            wq_group = wq_group or (
                DEFAULT_GROUP_Q4 if variant_bits == 4 else DEFAULT_GROUP
            )
        t0 = time.perf_counter()
        loop = asyncio.get_running_loop()

        def _build():
            import jax

            from dnet_tpu.core.kvcache import resolve_kv_bits
            from dnet_tpu.core.weights import plan_policy
            from dnet_tpu.models import ModelConfig, get_ring_model_cls
            from dnet_tpu.utils.checkpoint import Checkpoint

            kv_dtype, kv_quant_bits = resolve_kv_bits(self.kv_bits)
            cfg = ModelConfig.from_hf(Checkpoint(model_dir).config)
            # draft-MODEL speculation: local-engine single-sequence
            # serving only (batched/mesh engines draft by prompt-lookup)
            draft_dir = None
            draft_id = get_settings().api.draft_model
            if draft_id and self.spec_lookahead > 0 and self.mesh is None:
                draft_dir = resolve_model_dir(draft_id, self.models_dir)
                if draft_dir is None:
                    log.warning(
                        "DNET_API_DRAFT_MODEL=%s not found; drafting by "
                        "prompt-lookup instead", draft_id,
                    )
            plan = serving_plan(
                # a model built to be asked and dropped BEFORE the engine
                # loads: its few device arrays (rope table, layer kinds)
                # must not sit in front of the weights and shift where
                # they and the pool land
                get_ring_model_cls(cfg.model_type)(cfg, range(cfg.num_hidden_layers)),
                mesh=self.mesh,
                batch_slots=self.batch_slots,
                # LocalEngine's own policy for a load with no window given
                streams_weights=plan_policy(cfg.num_hidden_layers).streams_weights,
                kv_quant_bits=kv_quant_bits,
                spec_lookahead=self.spec_lookahead,
                draft_dir=draft_dir,
                max_seq=max_seq or self.max_seq,
                n_devices=len(jax.devices()) if self.mesh is not None else 0,
            )
            from dnet_tpu.core.batch import KV_DENSE

            # dense slots are a fallback or a refusal: said loudly
            (log.warning if plan.kv == KV_DENSE else log.info)(
                "serving %s through %s + %s (%s)",
                model_id, plan.engine, plan.adapter, plan.reason,
            )
            common = dict(
                max_seq=max_seq or self.max_seq,
                param_dtype=self.param_dtype,
                kv_dtype=kv_dtype,
                kv_quant_bits=kv_quant_bits,
                weight_quant_bits=wq_bits,
                prefix_cache_size=self.prefix_cache,
            )
            if plan.engine == "PipelinedMeshEngine":
                if self.spec_lookahead:
                    log.warning(
                        "DNET_API_SPEC_LOOKAHEAD is not supported by the "
                        "pipelined mesh engine (per-slot acceptance "
                        "lengths diverge); disabled"
                    )
                # staggered-microbatch pipeline: batch_slots concurrent
                # sequences keep every pp rank busy every stage-step
                from dnet_tpu.parallel.pipelined import PipelinedMeshEngine

                engine = PipelinedMeshEngine(
                    model_dir,
                    pp=self.mesh.get("pp", 0),
                    tp=self.mesh.get("tp", 1),
                    sp=self.mesh.get("sp", 1),
                    dp=self.mesh.get("dp", 1),
                    slots=self.batch_slots,
                    quant_group=wq_group,
                    **common,
                )
                return plan, engine, load_tokenizer(model_dir)
            if plan.engine == "MeshEngine":
                from dnet_tpu.parallel.engine import MeshEngine

                engine = MeshEngine(
                    model_dir,
                    pp=self.mesh.get("pp", 0),
                    tp=self.mesh.get("tp", 1),
                    dp=self.mesh.get("dp", 1),
                    sp=self.mesh.get("sp", 1),
                    quant_group=wq_group,
                    spec_lookahead=self.spec_lookahead,
                    **common,
                )
            elif plan.engine == "BatchedEngine":
                from dnet_tpu.core.batch import BatchedEngine

                # per-lane acceptance (r4): greedy lanes speculate and
                # advance unevenly; sampled lanes take the plain batched step
                engine = BatchedEngine(
                    model_dir,
                    slots=get_settings().sched.sched_slots
                    or max(self.batch_slots, 8),
                    weight_quant_group=wq_group,
                    spec_lookahead=self.spec_lookahead,
                    **common,
                )
            else:
                from dnet_tpu.core.engine import LocalEngine

                engine = LocalEngine(
                    model_dir,
                    weight_quant_group=wq_group,
                    spec_lookahead=self.spec_lookahead,
                    draft_dir=draft_dir,
                    **common,
                )
            # compile the decode programs now (the mesh chunk programs are
            # the most expensive compiles in the codebase), not on the
            # first request while every lane shares one executor
            if get_settings().api.warm_on_load:
                engine.warm_chunks()
            return plan, engine, load_tokenizer(model_dir)

        plan, engine, tokenizer = await loop.run_in_executor(None, _build)

        # swap adapter engine atomically
        old_adapter = self.inference.adapter
        if plan.adapter == "SchedulerAdapter":
            from dnet_tpu.sched import SchedulerAdapter as adapter_cls
        elif plan.adapter == "BatchedLocalAdapter":
            from dnet_tpu.api.strategies import BatchedLocalAdapter as adapter_cls
        else:
            from dnet_tpu.api.strategies import LocalAdapter as adapter_cls
        adapter = adapter_cls(engine)
        await adapter.start()
        self.inference.adapter = adapter
        self.inference.tokenizer = tokenizer
        self.inference.model_id = model_id
        self.engine = engine
        self.serving = plan
        self.model_dir = model_dir
        if old_adapter is not None:
            await old_adapter.shutdown()
        dt = time.perf_counter() - t0
        log.info("loaded model %s from %s in %.1fs", model_id, model_dir, dt)
        return dt

    async def unload_model(self) -> None:
        self.inference.model_id = None
        self.inference.tokenizer = None
        adapter = self.inference.adapter
        if adapter is not None:
            await adapter.shutdown()
        self.engine = None
        self.serving = None
        self.model_dir = None
        import gc

        gc.collect()
