"""What serves a loaded model is decided once, in code
(api/model_manager.py: serving_plan; its rule 4 is core/batch.py:
kv_layout): a case a rule and a refusal, then the served default: a load
with no DNET_* variable set goes through the scheduler over the pool
attended in place, and /health says so.
"""

import asyncio
import os

import pytest

from dnet_tpu.api.model_manager import ServingPlan, serving_plan
from dnet_tpu.config import reset_settings_cache

pytestmark = pytest.mark.api


def _model(model_dir, **attrs):
    from dnet_tpu.models import ModelConfig, get_ring_model_cls
    from dnet_tpu.utils.checkpoint import Checkpoint

    cfg = ModelConfig.from_hf(Checkpoint(model_dir).config)
    model = get_ring_model_cls(cfg.model_type)(cfg, range(cfg.num_hidden_layers))
    for k, v in attrs.items():
        setattr(model, k, v)
    return model


@pytest.fixture(scope="module")
def gpt_oss_dir(tmp_path_factory):
    from tests.fakes.checkpoints import make_tiny_gpt_oss

    d = tmp_path_factory.mktemp("plan_gpt_oss")
    make_tiny_gpt_oss(d)
    return d


BATCHED = ("BatchedEngine", "SchedulerAdapter")
LOCAL = ("LocalEngine", "LocalAdapter")

# (id, what differs from a plain load, engine, adapter, kv, a piece of the reason)
CASES = [
    ("default", {}, *BATCHED, "paged", "attended in place"),
    ("mesh-one-sequence", {"mesh": {"pp": 2, "tp": 1, "dp": 1, "sp": 1}},
     "MeshEngine", "LocalAdapter", "mesh", "batch_slots is 1"),
    ("mesh-pipelined", {"mesh": {"pp": 2, "tp": 1, "dp": 1, "sp": 1}, "batch_slots": 4},
     "PipelinedMeshEngine", "BatchedLocalAdapter", "mesh", "4 slots fill the pipeline"),
    ("mesh-lanes-do-not-divide", {"mesh": {"pp": 2, "tp": 1, "dp": 2, "sp": 1}, "batch_slots": 3},
     "MeshEngine", "LocalAdapter", "mesh", "not divisible by dp=2"),
    ("mesh-pp-inferred-too-deep", {"mesh": {"pp": 0, "tp": 1, "dp": 1, "sp": 1},
                                   "batch_slots": 2, "n_devices": 4},
     "MeshEngine", "LocalAdapter", "mesh", "cannot fill the pipeline"),
    ("streaming-weights", {"streams_weights": True}, *LOCAL, "dense", "weights stream from disk"),
    ("no-kv-commit", {"model": {"supports_kv_commit": False}}, *LOCAL, "dense",
     "no gated KV writes"),
    ("draft-model", {"draft_dir": "/some/draft", "spec_lookahead": 3}, *LOCAL, "dense",
     "a draft model speculates"),
    ("draft-model-without-lookahead", {"draft_dir": "/some/draft"}, *BATCHED, "paged",
     "attended in place"),
    ("spec-lookahead", {"spec_lookahead": 3}, *BATCHED, "dense",
     "per-lane speculation needs the dense cache"),
    ("kv-bits-8", {"kv_quant_bits": 8}, *BATCHED, "dense", "quantized KV cache (bits=8)"),
    ("no-paged-attend-hook", {"model_dir": "gpt_oss"}, *BATCHED, "dense", "no paged-attend hook"),
    ("window-layers-alone", {"model": {"paged_kinds": ("window", "window")}}, *BATCHED, "dense",
     "no full layer among the window layers"),
    ("pool-refuses-max-seq", {"max_seq": 72}, *BATCHED, "dense", "must be >= 1 and divide"),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_serving_plan_holds_each_rule_and_refusal(tiny_llama_dir, gpt_oss_dir, case):
    _id, over, engine, adapter, kv, why = case
    over = dict(over)
    model_dir = gpt_oss_dir if over.pop("model_dir", None) else tiny_llama_dir
    args = dict(
        mesh=None, batch_slots=1, streams_weights=False, kv_quant_bits=0,
        spec_lookahead=0, draft_dir=None, max_seq=64,
    )
    model = _model(model_dir, **over.pop("model", {}))
    args.update(over)
    plan = serving_plan(model, **args)
    assert isinstance(plan, ServingPlan)
    assert (plan.engine, plan.adapter, plan.kv) == (engine, adapter, kv)
    assert why in plan.reason, plan.reason


def test_a_plain_load_serves_the_scheduler_over_the_pool(tiny_llama_dir, monkeypatch):
    """No DNET_* variable at all: load_model builds what serving_plan says,
    a request runs through the in-place step, and /health shows the kernel
    used and the reason line."""
    from aiohttp.test_utils import TestClient, TestServer

    from dnet_tpu.api.http import ApiHTTPServer
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.api.model_manager import LocalModelManager
    from dnet_tpu.core.batch import BatchedEngine
    from dnet_tpu.sched import SchedulerAdapter

    for k in [k for k in os.environ if k.startswith("DNET_")]:
        monkeypatch.delenv(k)
    monkeypatch.setenv("DNET_ENV_FILE", os.devnull)  # and no .env either
    reset_settings_cache()

    async def go():
        inference = InferenceManager(adapter=None, request_timeout_s=60.0)
        manager = LocalModelManager(inference, max_seq=64, param_dtype="float32")
        server = ApiHTTPServer(inference, manager)
        client = TestClient(TestServer(server.app))
        await client.start_server()
        try:
            health = await (await client.get("/health")).json()
            assert "serving" not in health  # nothing loaded yet
            used0 = sum(v for k, v in health["kernels"]["paged_attend"].items()
                        if k != "dense_shapes")
            r = await client.post("/v1/load_model", json={"model": str(tiny_llama_dir)})
            assert r.status == 200, await r.text()
            assert isinstance(inference.adapter, SchedulerAdapter)
            eng = manager.engine
            assert isinstance(eng, BatchedEngine) and eng.slots == 8
            assert eng.kv is None and eng.kv_pool is not None
            r = await client.post("/v1/chat/completions", json={
                "model": "tiny", "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 3, "temperature": 0,
            })
            assert r.status == 200, await r.text()
            health = await (await client.get("/health")).json()
            assert health["serving"] == {
                "engine": "BatchedEngine", "adapter": "SchedulerAdapter", "kv": "paged",
                "reason": manager.serving.reason,
            }
            assert "attended in place" in health["serving"]["reason"]
            paged = health["kernels"]["paged_attend"]
            assert sum(v for k, v in paged.items() if k != "dense_shapes") > used0
            assert paged["dense"] == 0
        finally:
            await client.close()
            await manager.unload_model()
        assert manager.serving is None

    try:
        asyncio.run(go())
    finally:
        monkeypatch.undo()
        reset_settings_cache()
