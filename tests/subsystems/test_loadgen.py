"""Serving-grade load harness (dnet_tpu/loadgen/).

Tiers: pure units (schedule determinism, report math, percentile edges,
exposition parsing), an overload run over a fake adapter asserting the
shed/SLO-attainment report surface under chaos-injected admission delay,
and the ACCEPTANCE smoke: a seeded in-process load run against the real
BatchedEngine over the paged pool whose report must cross-validate
against the live `dnet_slo_*` gauges and whose phase breakdown must
account for the parent decode-step time.
"""

import asyncio
import json

import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.loadgen import (
    Bucket,
    RequestOutcome,
    WorkloadSpec,
    build_report,
    parse_buckets,
    parse_prometheus,
    percentile,
    run_load,
    schedule,
)
from dnet_tpu.obs import get_recorder, metric, reset_obs
from dnet_tpu.obs.phases import DECODE_CHILD_SPANS

pytestmark = pytest.mark.api


def run(coro):
    return asyncio.run(coro)


# ---- workload determinism --------------------------------------------------


def test_same_seed_identical_schedule():
    spec = WorkloadSpec(seed=42, requests=32, rate_rps=10.0,
                        buckets=parse_buckets("8:16,32:8,64:4", "3,2,1"))
    a, b = schedule(spec), schedule(spec)
    assert a == b  # arrival times, prompts, budgets, seeds — all of it
    assert len(a) == 32
    assert a[0].t_s == 0.0
    assert all(y.t_s > x.t_s for x, y in zip(a, a[1:]))  # strictly ordered
    # prompts honor the bucket's nominal token length (byte-exact)
    for p in a:
        assert len(p.prompt) == p.prompt_tokens


def test_different_seed_different_schedule():
    base = dict(requests=16, rate_rps=10.0)
    a = schedule(WorkloadSpec(seed=1, **base))
    b = schedule(WorkloadSpec(seed=2, **base))
    assert [p.t_s for p in a] != [p.t_s for p in b]
    assert [p.prompt for p in a] != [p.prompt for p in b]


def test_fixed_arrival_spacing_exact():
    spec = WorkloadSpec(seed=0, requests=5, rate_rps=4.0, arrival="fixed")
    plan = schedule(spec)
    assert [round(p.t_s, 6) for p in plan] == [0.0, 0.25, 0.5, 0.75, 1.0]


def test_bucket_parse_and_validation():
    bs = parse_buckets("8:16,32:8", "3,1")
    assert bs == (Bucket(8, 16, 3.0), Bucket(32, 8, 1.0))
    with pytest.raises(ValueError):
        parse_buckets("")
    with pytest.raises(ValueError):
        parse_buckets("8x16")  # wrong separator
    with pytest.raises(ValueError):
        parse_buckets("8:16", "1,2")  # weight count mismatch
    with pytest.raises(ValueError):
        WorkloadSpec(arrival="lognormal")
    with pytest.raises(ValueError):
        Bucket(0, 4)


def test_spec_from_settings(monkeypatch):
    monkeypatch.setenv("DNET_LOADGEN_SEED", "9")
    monkeypatch.setenv("DNET_LOADGEN_REQUESTS", "3")
    monkeypatch.setenv("DNET_LOADGEN_BUCKETS", "4:2")
    reset_settings_cache()
    try:
        spec = WorkloadSpec.from_settings()
        assert spec.seed == 9 and spec.requests == 3
        assert spec.buckets == (Bucket(4, 2),)
    finally:
        monkeypatch.undo()
        reset_settings_cache()


# ---- percentile / report math ---------------------------------------------


def test_percentile_edge_cases():
    assert percentile([], 0.5) == 0.0
    assert percentile([7.0], 0.0) == 7.0
    assert percentile([7.0], 1.0) == 7.0
    vals = [float(v) for v in range(1, 101)]
    assert percentile(vals, 0.50) == 50.0  # nearest-rank, not interpolated
    assert percentile(vals, 0.95) == 95.0
    assert percentile(vals, 0.99) == 99.0
    assert percentile(vals, 1.0) == 100.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
    with pytest.raises(ValueError):
        percentile([1.0], 1.5)


def _row(i, *, t=10.0, status=200, ok=True, shed=False, reason="",
         tokens=0, ttft=50.0, e2e=200.0, itl=()):
    return RequestOutcome(
        index=i, t_sched_s=t, t_start_s=t, status=status, ok=ok,
        shed=shed, shed_reason=reason, tokens_out=tokens, ttft_ms=ttft,
        e2e_ms=e2e, itl_ms=list(itl),
    )


def test_report_goodput_excludes_shed_failed_and_warmup():
    spec = WorkloadSpec(seed=0, requests=8, rate_rps=1.0, warmup_s=5.0)
    rows = [
        _row(0, t=1.0, tokens=100),               # warmup: excluded entirely
        _row(1, tokens=10, itl=(5.0, 6.0)),
        _row(2, tokens=20, itl=(7.0,)),
        _row(3, status=429, ok=False, shed=True, reason="queue_full"),
        _row(4, status=503, ok=False, shed=True, reason="draining"),
        _row(5, status=504, ok=False, shed=True, reason="deadline"),
        _row(6, status=429, ok=False, shed=True, reason="queue_full"),
        _row(7, status=200, ok=False),            # failed mid-stream
    ]
    rep = build_report(rows, spec=spec, duration_s=15.0)
    r = rep["requests"]
    assert r["measured"] == 7 and r["warmup_excluded"] == 1
    assert r["completed"] == 2 and r["failed"] == 1 and r["shed"] == 4
    assert r["shed_by_status"] == {"429": 2, "503": 1, "504": 1}
    assert r["shed_by_reason"] == {"queue_full": 2, "draining": 1,
                                   "deadline": 1}
    assert r["shed_rate"] == round(4 / 7, 4)
    # goodput: ONLY the two completed rows' tokens, over duration - warmup
    assert rep["goodput"]["tokens_out"] == 30
    assert rep["goodput"]["tok_s"] == 3.0  # 30 tokens / 10s window
    # availability over ADMITTED work: 2 completed / (2 + 1 failed)
    assert rep["availability"] == round(2 / 3, 4)
    # latency aggregates come from completed rows only
    assert rep["latency_ms"]["ttft"]["n"] == 2
    assert rep["latency_ms"]["tpot"]["n"] == 3
    # the report is JSON-serializable as emitted
    json.dumps(rep)


def test_report_all_shed_zero_goodput():
    spec = WorkloadSpec(seed=0, requests=2, rate_rps=1.0)
    rows = [_row(0, status=429, ok=False, shed=True, reason="queue_full"),
            _row(1, status=429, ok=False, shed=True, reason="queue_full")]
    rep = build_report(rows, spec=spec, duration_s=4.0)
    assert rep["goodput"]["tokens_out"] == 0
    assert rep["goodput"]["tok_s"] == 0.0
    assert rep["availability"] == 1.0  # vacuous: nothing was admitted
    assert rep["latency_ms"]["ttft"]["p99_ms"] == 0.0


def test_classify_shed_matches_server_messages():
    """The markers must match what the server actually puts in
    error.message — notably the queue-timeout text is 'no slot within
    Xs', not the enum name."""
    from dnet_tpu.loadgen.client import classify_shed

    assert classify_shed(
        429, "admission queue full (2 waiting, 1 executing)"
    ) == "queue_full"
    assert classify_shed(
        429, "no slot within 10.0s (DNET_ADMIT_QUEUE_TIMEOUT_S)"
    ) == "queue_timeout"
    assert classify_shed(503, "server is draining for shutdown") == "draining"
    assert classify_shed(
        504, "request deadline expired after 3 token(s)"
    ) == "deadline"
    assert classify_shed(429, "paged KV pool exhausted") == "backpressure"
    assert classify_shed(429, "") == "backpressure"
    assert classify_shed(503, "ring degraded: shard(s) ...") == "degraded"


def test_parse_prometheus_and_deltas():
    from dnet_tpu.loadgen.report import metric_delta

    text = (
        "# HELP dnet_x_total help\n"
        "# TYPE dnet_x_total counter\n"
        "dnet_x_total 41\n"
        'dnet_span_ms_sum{span="dnet.decode.launch"} 12.5\n'
        'dnet_span_ms_count{span="dnet.decode.launch"} 3\n'
        "garbage line without value\n"
    )
    d = parse_prometheus(text)
    assert d["dnet_x_total"] == 41.0
    assert d['dnet_span_ms_sum{span="dnet.decode.launch"}'] == 12.5
    assert "garbage" not in "".join(d)
    before = {"dnet_x_total": 40.0}
    assert metric_delta(d, before, "dnet_x_total") == 1.0
    assert metric_delta(d, None, "dnet_missing") == 0.0


# ---- overload run over a fake adapter (chaos-injected admission delay) -----


class _ScriptAdapter:
    """Minimal ApiAdapterBase-alike: resolves each step with the next
    scripted token after a fixed delay (the decode-time knob)."""

    def __init__(self, script, token_delay_s=0.0):
        from dnet_tpu.api.strategies import _TokenFutures

        self.script = list(script)
        self.token_delay_s = token_delay_s
        self._futures = _TokenFutures()
        self._scripts = {}

    async def start(self):
        pass

    async def shutdown(self):
        pass

    async def reset_cache(self, nonce):
        self._scripts.pop(nonce, None)

    def set_deadline(self, nonce, deadline_ts):
        pass

    def fail_pending(self, error):
        pass

    def max_seq(self):
        return None

    async def send_tokens(self, nonce, token_ids, decoding, step, budget=None):
        from dnet_tpu.core.types import TokenResult

        self._futures.expect(nonce, step)
        script = self._scripts.setdefault(nonce, list(self.script))

        async def produce():
            if self.token_delay_s:
                await asyncio.sleep(self.token_delay_s)
            tok = script.pop(0) if script else 257  # EOS when exhausted
            self._futures.resolve(
                TokenResult(nonce=nonce, token_id=tok, step=step)
            )

        asyncio.ensure_future(produce())

    async def await_token(self, nonce, step, timeout):
        return await self._futures.wait(nonce, step, timeout)


class _FakeModelManager:
    current_model_id = "fake"


def _http_stack(adapter, admission):
    from dnet_tpu.api.http import ApiHTTPServer
    from dnet_tpu.api.inference import InferenceManager
    from dnet_tpu.utils.tokenizer import ByteTokenizer

    inference = InferenceManager(
        adapter=adapter, request_timeout_s=30.0, admission=admission
    )
    inference.tokenizer = ByteTokenizer()
    inference.model_id = "fake"
    return inference, ApiHTTPServer(inference, _FakeModelManager())


async def _test_client(server):
    from aiohttp.test_utils import TestClient, TestServer

    client = TestClient(TestServer(server.app))
    await client.start_server()
    return client


def test_chaos_overload_report_reflects_shed_and_burn(monkeypatch):
    """Degraded serving under load: chaos delays admission, capacity 1 with
    a depth-1 queue sheds the burst, and an absurd TTFT target burns.  The
    report must carry all three: the 429 breakdown by admission reason,
    goodput from completed rows only, and slo attained=False — while LIVE
    availability stays 1.0 (shed work is not failed work)."""
    from dnet_tpu.admission.controller import AdmissionController
    from dnet_tpu.resilience.chaos import clear_chaos, install_chaos

    monkeypatch.setenv("DNET_OBS_SLO_TTFT_P95_MS", "0.001")  # always burns
    monkeypatch.setenv("DNET_OBS_SLO_AVAILABILITY", "0.5")
    reset_settings_cache()
    reset_obs()
    install_chaos("admit:delay:50ms", seed=3)
    try:

        async def go():
            adapter = _ScriptAdapter(list(b"ok"), token_delay_s=0.02)
            admission = AdmissionController(
                1, queue_depth=1, queue_timeout_s=30.0
            )
            inference, server = _http_stack(adapter, admission)
            client = await _test_client(server)
            try:
                spec = WorkloadSpec(
                    seed=11, requests=8, rate_rps=500.0,  # a burst
                    buckets=(Bucket(4, 4),), timeout_s=30.0,
                )
                result = await run_load(client, spec, "fake")
                return result.report
            finally:
                await client.close()

        rep = run(go())
        r = rep["requests"]
        assert r["completed"] >= 2  # the slot + the queued request
        assert r["shed"] >= 1 and r["failed"] == 0
        assert set(r["shed_by_status"]) == {"429"}
        assert set(r["shed_by_reason"]) <= {"queue_full", "queue_timeout"}
        assert r["completed"] + r["shed"] == r["measured"]
        # goodput counts only completed streams (2 content tokens each
    # + the EOS step is not a content token)
        assert rep["goodput"]["tokens_out"] == sum(
            row["tokens_out"] for row in rep["rows"] if row["ok"]
        )
        # injected overload is visible: the chaos counter moved
        assert metric("dnet_chaos_injected_total").labels(
            point="admit").value >= 1
        # SLO attainment reflects the burn; availability did NOT burn —
        # admission sheds never enter the availability window
        assert rep["slo"]["attained"] is False
        assert "ttft_p95_ms" in rep["slo"]["burning"]
        assert rep["slo"]["cross_check"]["availability"]["live"] == 1.0
        assert rep["slo"]["cross_check"]["availability"]["report"] == 1.0
    finally:
        clear_chaos()
        monkeypatch.undo()
        reset_settings_cache()
        reset_obs()


# ---- ACCEPTANCE: seeded in-process smoke load run (real engine, paged) -----


def test_inprocess_smoke_load_acceptance(tiny_llama_dir, monkeypatch):
    """The tier-1 acceptance run: real BatchedEngine over the paged pool
    behind the real admission/SSE stack, seeded open-loop load through the
    real loadgen client.  Asserts the BENCH_SERVE contract: goodput over
    200-completed only, TTFT/decode p95 and availability cross-validating
    against the live dnet_slo_* gauges, and the decode dispatch's host
    spans (always on, unfenced) summing to the parent decode-step time."""
    reset_settings_cache()
    reset_obs()
    try:

        async def go():
            from dnet_tpu.api.strategies import BatchedLocalAdapter
            from dnet_tpu.core.batch import BatchedEngine
            from dnet_tpu.utils.tokenizer import load_tokenizer

            eng = BatchedEngine(
                tiny_llama_dir, slots=4, max_seq=64, param_dtype="float32"
            )
            assert eng.kv_pool is not None  # the pool, attended in place: derived
            adapter = BatchedLocalAdapter(eng)
            from dnet_tpu.admission.controller import AdmissionController
            from dnet_tpu.api.http import ApiHTTPServer
            from dnet_tpu.api.inference import InferenceManager

            inference = InferenceManager(
                adapter=adapter, request_timeout_s=120.0,
                admission=AdmissionController(
                    4, queue_depth=32, queue_timeout_s=60.0
                ),
            )
            inference.tokenizer = load_tokenizer(tiny_llama_dir)
            inference.model_id = "tiny"
            server = ApiHTTPServer(inference, _FakeModelManager())
            await adapter.start()
            client = await _test_client(server)
            try:
                buckets = (Bucket(6, 4), Bucket(12, 3))
                # two warmup passes absorb every compile — a bursty one and
                # a steady one, so both batch compositions (and therefore
                # every pow2 scatter width / chunk bucket the measured run
                # can hit) are traced before measurement.  Then the windows
                # reset so the live SLO gauges and the report describe the
                # SAME population.
                for wseed, wrate in ((1, 50.0), (2, 10.0)):
                    warm = WorkloadSpec(
                        seed=wseed, requests=6, rate_rps=wrate,
                        buckets=buckets, timeout_s=120.0,
                    )
                    await run_load(client, warm, "tiny")
                reset_obs()
                spec = WorkloadSpec(
                    seed=5, requests=10, rate_rps=8.0, buckets=buckets,
                    timeout_s=120.0,
                )
                result = await run_load(client, spec, "tiny")
                rep = result.report

                # -- every measured request completed as a real 200 stream
                r = rep["requests"]
                assert r["completed"] == 10, rep["rows"]
                assert r["shed"] == 0 and r["failed"] == 0
                toks = sum(
                    row["tokens_out"] for row in rep["rows"] if row["ok"]
                )
                assert rep["goodput"]["tokens_out"] == toks > 0

                # -- cross-validation vs the live dnet_slo_* gauges
                cross = rep["slo"]["cross_check"]
                assert cross["availability"]["report"] == 1.0
                assert cross["availability"]["live"] == 1.0
                ttft = cross["ttft_p95_ms"]
                assert ttft["live"] > 0
                # client-side includes HTTP + admission wait; the tolerance
                # pins the same order of magnitude (steady-state gap is
                # ~15%, but shared-CPU CI can stall either side)
                assert abs(ttft["report"] - ttft["live"]) <= max(
                    1.0 * ttft["live"], 100.0
                ), ttft
                dec = cross["decode_p95_ms"]
                assert dec["live"] > 0
                assert abs(dec["report"] - dec["live"]) <= max(
                    1.0 * dec["live"], 50.0
                ), dec
                # p99 peers exist on both sides
                assert rep["slo"]["live_p99"]["ttft_ms"] > 0
                assert metric("dnet_slo_ttft_p99_ms").value > 0

                # -- the dispatch's host spans account for the parent
                # decode step (the four spans of the in-place step)
                pa = rep["phase_attribution"]
                assert tuple(pa["phases"]) == DECODE_CHILD_SPANS
                assert len(DECODE_CHILD_SPANS) == 4
                for ph in DECODE_CHILD_SPANS:
                    assert pa["phases"][ph]["count"] > 0, pa
                assert pa["decode_step"]["count"] > 0
                assert 0.9 <= pa["coverage"] <= 1.1, pa

                # -- now force sheds and prove they stay out of goodput
                inference.admission = AdmissionController(
                    1, queue_depth=0, queue_timeout_s=1.0
                )
                burst = WorkloadSpec(
                    seed=6, requests=6, rate_rps=1000.0,
                    buckets=(Bucket(6, 3),), timeout_s=120.0,
                )
                shed_rep = (await run_load(client, burst, "tiny")).report
                sr = shed_rep["requests"]
                assert sr["shed"] >= 1
                assert "429" in sr["shed_by_status"]
                assert shed_rep["goodput"]["tokens_out"] == sum(
                    row["tokens_out"]
                    for row in shed_rep["rows"] if row["ok"]
                )
                # shed work is not failed work: live availability holds
                assert (
                    shed_rep["slo"]["cross_check"]["availability"]["live"]
                    == 1.0
                )
                return rep
            finally:
                await client.close()
                await adapter.shutdown()
                eng.close()

        run(go())
        # the per-dispatch spans are process-wide histograms now, not rows
        # on every participating request's timeline; a timeline keeps the
        # request's own story
        rec = get_recorder()
        names = {
            s["name"]
            for rid in rec.request_ids()
            for s in (rec.timeline(rid) or {"spans": []})["spans"]
        }
        assert {"ttft", "decode_step", "request"} <= names
        assert not names & {"kv_gather", "compute", "kv_scatter", "sample"}
    finally:
        monkeypatch.undo()
        reset_settings_cache()
        reset_obs()
