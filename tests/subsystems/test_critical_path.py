"""Critical-path attribution, tick flight-recording, and trace export.

Covers the segment-ledger units (partition-by-construction, nesting
priority, gap -> other, shard-node remap, the admission_wait negative
offset), the PR 4 resume-nonce aliasing regression, the scheduler tick
flight-recorder ring, the Perfetto/Chrome trace export schema (including
cross-hop flow-event pairing), the bench_compare delta/threshold math,
and the ACCEPTANCE run: an in-process two-shard ring through the real
HTTP server whose per-request segment sums must reconcile against the
client-measured E2E, whose exported trace must carry cross-hop flow
events, and whose /v1/debug/sched ring must agree with the
dnet_sched_* counters.
"""

import asyncio
import json
import os
import time

import pytest

from dnet_tpu.config import reset_settings_cache
from dnet_tpu.loadgen.compare import (
    FailRule,
    compare_records,
    diff_leg,
    legs,
    parse_fail_rule,
    rule_violation,
)
from dnet_tpu.obs import get_recorder, metric, reset_obs
from dnet_tpu.obs.critical_path import SPAN_SEGMENTS, decompose
from dnet_tpu.obs.phases import (
    REQUEST_SEGMENTS,
    SEG_ADMISSION_WAIT,
    SEG_DECODE_COMPUTE,
    SEG_HOP_RTT,
    SEG_OTHER,
    SEG_SSE_FLUSH,
    SEG_SHARD_COMPUTE,
    SEG_WIRE_ENCODE,
)
from dnet_tpu.obs.recorder import FlightRecorder, base_rid
from dnet_tpu.obs.trace import export_trace
from dnet_tpu.sched.flight import TickFlightRecorder, get_tick_recorder
from dnet_tpu.sched.kinds import QUEUE_STATES

pytestmark = pytest.mark.api


@pytest.fixture(autouse=True)
def _obs_env():
    """Every test leaves the obs env exactly as it found it."""
    keys = ("DNET_OBS_ENABLED", "DNET_OBS_TICK_RECORDS", "DNET_PROFILE")
    saved = {k: os.environ.get(k) for k in keys}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    reset_settings_cache()


def _tl(spans, rid="r-test", cluster=False):
    tl = {"rid": rid, "t_unix": 1000.0, "spans": spans, "dropped": 0}
    if cluster:
        tl["cluster"] = True
    return tl


def _span(name, t, dur, **extra):
    s = {"name": name, "t_ms": float(t), "dur_ms": float(dur)}
    s.update(extra)
    return s


# ---- segment decomposition units ------------------------------------------


def test_decompose_partitions_window_most_specific_wins():
    """Nested spans never double-count: each elementary slice goes to the
    most specific active span, and the segment sum equals the window."""
    led = decompose(_tl([
        _span("request", 0, 100),
        _span("decode_step", 0, 100),   # tier-1 umbrella
        _span("hop_rtt", 10, 40),       # tier 2, inside the umbrella
        _span("sse_flush", 20, 5),      # tier-4 leaves inside the hop
        _span("wire_encode", 30, 5),
    ]))
    seg = led["segments_ms"]
    assert set(seg) == set(REQUEST_SEGMENTS)
    assert seg[SEG_DECODE_COMPUTE] == 60.0   # 100 minus the hop's 40
    assert seg[SEG_HOP_RTT] == 30.0          # 40 minus the two leaves
    assert seg[SEG_SSE_FLUSH] == 5.0
    assert seg[SEG_WIRE_ENCODE] == 5.0
    assert led["total_ms"] == 100.0
    assert led["e2e_ms"] == 100.0
    assert led["coverage"] == 1.0
    assert led["dominant"] == SEG_DECODE_COMPUTE
    assert round(sum(seg.values()), 3) == led["total_ms"]


def test_decompose_gaps_land_in_other():
    """Recorded time no span claims is attributed, not dropped."""
    led = decompose(_tl([
        _span("request", 0, 40),
        _span("decode_step", 0, 10),
        _span("sse_flush", 20, 10),
    ]))
    seg = led["segments_ms"]
    assert seg[SEG_DECODE_COMPUTE] == 10.0
    assert seg[SEG_SSE_FLUSH] == 10.0
    assert seg[SEG_OTHER] == 20.0  # [10,20) gap + [30,40) tail
    assert led["total_ms"] == 40.0


def test_decompose_shard_node_remaps_compute():
    """On a stitched timeline, generic decode-compute spans recorded by a
    shard are shard_compute, not the API driver's decode_compute."""
    led = decompose(_tl([
        _span("request", 0, 20),
        _span("decode_sync_drain", 0, 10, node="s0"),
        _span("decode_sync_drain", 10, 10, node="api"),
    ], cluster=True))
    seg = led["segments_ms"]
    assert seg[SEG_SHARD_COMPUTE] == 10.0
    assert seg[SEG_DECODE_COMPUTE] == 10.0
    assert led["cluster"] is True


def test_decompose_admission_wait_extends_window_left():
    """The gate wait happens before t=0 (the admitted window origin); the
    ledger window stretches left to carry it and coverage says so."""
    led = decompose(_tl([
        _span("request", 0, 100),
        _span("admission_wait", -50, 50),
        _span("decode_step", 0, 100),
    ]))
    seg = led["segments_ms"]
    assert seg[SEG_ADMISSION_WAIT] == 50.0
    assert seg[SEG_DECODE_COMPUTE] == 100.0
    assert led["total_ms"] == 150.0
    assert led["e2e_ms"] == 100.0   # the request span's measured duration
    assert led["coverage"] == 1.5   # wait rode on top of the e2e window


def test_decompose_degenerate_timelines():
    assert decompose(None) is None
    assert decompose({"rid": "x", "t_unix": 0.0, "spans": []}) is None
    # unmapped marker spans alone attribute nothing
    assert decompose(_tl([_span("prefix_cache_hit", 0, 0)])) is None
    # a bare request span still yields a ledger (all of it unattributed)
    led = decompose(_tl([_span("request", 0, 30)]))
    assert led["segments_ms"][SEG_OTHER] == 30.0
    assert led["total_ms"] == 30.0 == led["e2e_ms"]


def test_span_segment_map_targets_are_declared():
    for name, (segment, prio) in SPAN_SEGMENTS.items():
        assert segment in REQUEST_SEGMENTS, name
        assert 1 <= prio <= 4, name


# ---- resume-nonce aliasing (PR 4 regression) -------------------------------


def test_resume_nonce_segments_alias_to_base_rid():
    """A resumed request's replay segments (`rid#rN` wire nonces) land on
    the BASE rid's timeline — one story, not fragments."""
    assert base_rid("chatcmpl-abc#r2") == "chatcmpl-abc"
    assert base_rid("chatcmpl-abc") == "chatcmpl-abc"
    rec = FlightRecorder()
    rec.begin("chatcmpl-abc")
    rec.span("chatcmpl-abc", "prefill", 5.0)
    rec.span("chatcmpl-abc#r1", "prefill", 7.0)   # resume segment 1
    rec.span("chatcmpl-abc#r2", "sample", 1.0)    # resume segment 2
    assert rec.request_ids() == ["chatcmpl-abc"]
    tl = rec.timeline("chatcmpl-abc")
    assert [s["name"] for s in tl["spans"]] == ["prefill", "prefill", "sample"]
    # lookups under a segment nonce resolve to the same timeline
    assert rec.timeline("chatcmpl-abc#r9")["rid"] == "chatcmpl-abc"


def test_request_ids_since_window():
    rec = FlightRecorder()
    rec.begin("a")
    rec.begin("b")
    assert rec.request_ids_since(0.0) == ["a", "b"]
    assert rec.request_ids_since(time.time() + 60.0) == []


# ---- scheduler tick flight-recorder ---------------------------------------


def _tick(t, **kw):
    base = dict(tick_ms=2.0, budget_tokens=10, prefill_tokens=4,
                decode_lanes=2, preempted=0, requeued=0, errors=0,
                queue_depths={"WAITING": 1})
    base.update(kw)
    return t.record(**base)


def test_tick_recorder_ring_bound_and_budget_math():
    t = TickFlightRecorder(capacity=3)
    before = metric("dnet_sched_tick_records_total").value
    for _ in range(5):
        rec = _tick(t)
    assert rec.budget_used == 6 and rec.budget_wasted == 4
    assert metric("dnet_sched_tick_records_total").value - before == 5
    snap = t.snapshot()
    assert snap["summary"]["ticks_captured"] == 5
    assert snap["summary"]["ticks_retained"] == 3
    assert snap["summary"]["capacity"] == 3
    assert [r["seq"] for r in snap["records"]] == [2, 3, 4]  # oldest evicted
    assert snap["summary"]["budget_used_ratio"] == 0.6
    assert snap["states"] == list(QUEUE_STATES)
    json.dumps(snap)  # the /v1/debug/sched payload is JSON-clean
    t.clear()
    empty = t.snapshot()
    assert empty["summary"]["ticks_captured"] == 0
    assert empty["records"] == []


def test_tick_recorder_capacity_from_env_and_disable():
    os.environ["DNET_OBS_TICK_RECORDS"] = "2"
    reset_settings_cache()
    t = TickFlightRecorder()  # lazy capacity: reads the knob per record
    assert t.capacity() == 2
    for _ in range(4):
        _tick(t)
    assert len(t.records()) == 2
    os.environ["DNET_OBS_TICK_RECORDS"] = "0"
    reset_settings_cache()
    assert _tick(t) is None  # 0 disables capture entirely
    assert len(t.records()) == 2


# ---- trace export ----------------------------------------------------------


def test_export_trace_schema_tracks_and_flows():
    """One process per node, named thread tracks, X/i events, and flow
    arrows pairing each tx span with the earliest later transport_recv of
    the same (rid, seq) — both hops of a ring frame, even when every span
    sits in one process-wide timeline."""
    tl = _tl([
        _span("prefill", 0, 4),
        _span("transport_send", 0, 2, meta={"seq": 1}),   # api -> s0
        _span("transport_recv", 3, 0, meta={"seq": 1}, node="s0"),
        _span("shard_tx", 5, 1, meta={"seq": 1}, node="s0"),  # s0 -> s1
        _span("transport_recv", 7, 0, meta={"seq": 1}, node="s1"),
    ], rid="r1")
    trace = export_trace([tl])
    events = trace["traceEvents"]
    json.dumps(trace)  # perfetto wants plain JSON

    procs = {e["args"]["name"]: e["pid"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs["api"] == 1
    assert set(procs) == {"api", "s0", "s1"}
    tnames = {e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tnames == {"driver", "compute", "tx-stage"}

    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert xs["prefill"]["dur"] == 4000.0      # microseconds
    assert xs["prefill"]["args"]["rid"] == "r1"
    assert all("ts" in e and "pid" in e and "tid" in e for e in events
               if e["ph"] != "M")
    instants = [e for e in events if e["ph"] == "i"]
    assert {e["name"] for e in instants} == {"transport_recv"}
    assert all(e["s"] == "t" for e in instants)
    # recorder meta kwargs are flattened into event args
    assert xs["transport_send"]["args"]["seq"] == 1

    starts = sorted((e for e in events if e["ph"] == "s"),
                    key=lambda e: e["ts"])
    finishes = sorted((e for e in events if e["ph"] == "f"),
                      key=lambda e: e["ts"])
    assert len(starts) == len(finishes) == 2  # both hops, exactly once
    # hop 0: send on api (ts 0) -> recv on s0 (ts 3ms); hop 1: shard_tx on
    # s0 (ts 5ms) -> recv on s1 (ts 7ms) — greedy earliest-rx-after-tx
    assert (starts[0]["ts"], finishes[0]["ts"]) == (0.0, 3000.0)
    assert (starts[1]["ts"], finishes[1]["ts"]) == (5000.0, 7000.0)
    assert starts[0]["id"] == "r1/1/0" and starts[1]["id"] == "r1/1/1"
    assert {f["id"] for f in finishes} == {"r1/1/0", "r1/1/1"}
    assert all(f["bp"] == "e" for f in finishes)

    assert trace["displayTimeUnit"] == "ms"
    other = trace["otherData"]
    assert other["timelines"] == 1 and "wire_overlap" in other
    assert "truncated_events" not in other


def test_export_trace_counters_and_truncation():
    tl = _tl([_span("prefill", 0, 4), _span("sample", 4, 1),
              _span("decode_step", 5, 2)])
    ticks = [{"t_unix": 1000.001, "queue_depths": {"WAITING": 2, "RUNNING": 1},
              "kv_blocks_used": 3, "kv_blocks_free": 5}]
    trace = export_trace([tl], tick_records=ticks)
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    by_name = {e["name"]: e for e in counters}
    assert by_name["sched queue depth"]["args"] == {"WAITING": 2, "RUNNING": 1}
    assert by_name["kv blocks"]["args"] == {"used": 3, "free": 5}
    assert trace["otherData"]["tick_records"] == 1

    capped = export_trace([tl], tick_records=ticks, max_events=2)
    non_meta = [e for e in capped["traceEvents"] if e["ph"] != "M"]
    assert len(non_meta) == 2
    assert capped["otherData"]["truncated_events"] == 3  # 5 events, kept 2
    # the cap keeps the EARLIEST events, so the dump front-truncates
    assert all(e["ts"] <= 4000.0 for e in non_meta)


# ---- bench_compare math ----------------------------------------------------


def _report(tok_s, p95, extra=None):
    rep = {
        "goodput": {"tok_s": tok_s, "tokens_out": 100},
        "availability": 1.0,
        "latency_ms": {"e2e": {"p95_ms": p95}},
        "requests": {"completed": 5, "shed": 0, "failed": 0,
                     "shed_rate": 0.0},
    }
    rep.update(extra or {})
    return rep


def test_parse_fail_rule_shapes():
    r = parse_fail_rule("goodput.tok_s=-5%")
    assert r == FailRule("goodput.tok_s", -1, 0.05, True)
    r = parse_fail_rule("latency_ms.e2e.p95_ms=+10%")
    assert (r.direction, r.limit, r.relative) == (1, 0.10, True)
    r = parse_fail_rule("requests.failed=+3")
    assert (r.direction, r.limit, r.relative) == (1, 3.0, False)
    assert "rise" in r.describe()
    for bad in ("goodput.tok_s", "a=5", "a=+5%%", "=+5%", "a=+"):
        with pytest.raises(ValueError):
            parse_fail_rule(bad)


def test_rule_violation_is_directional():
    rise = parse_fail_rule("latency_ms.e2e.p95_ms=+10%")
    assert rule_violation(rise, _report(10, 100), _report(10, 105)) is None
    assert rule_violation(rise, _report(10, 100), _report(10, 115))
    # an IMPROVEMENT never trips the gate, no matter how large
    assert rule_violation(rise, _report(10, 100), _report(10, 20)) is None
    fall = parse_fail_rule("goodput.tok_s=-5%")
    assert rule_violation(fall, _report(100, 1), _report(94, 1))
    assert rule_violation(fall, _report(100, 1), _report(96, 1)) is None
    assert rule_violation(fall, _report(100, 1), _report(300, 1)) is None
    absolute = parse_fail_rule("requests.failed=+3")
    old = _report(1, 1)
    worse = _report(1, 1, {"requests": {"failed": 4, "completed": 1,
                                        "shed": 0, "shed_rate": 0.0}})
    assert rule_violation(absolute, old, worse)
    # missing path in either record is itself a violation
    gone = parse_fail_rule("goodput.requests_per_s=+1")
    msg = rule_violation(gone, _report(1, 1), _report(1, 1))
    assert "missing" in msg
    # zero baseline: a relative rule fires on any bad-direction change
    zero = _report(0.0, 1)
    assert rule_violation(fall, zero, zero) is None
    assert rule_violation(parse_fail_rule("goodput.tok_s=+10%"),
                          zero, _report(5, 1))


def test_legs_flat_and_multi():
    flat = _report(10, 100)
    assert list(legs(flat)) == [""]
    multi = {"legacy": _report(10, 100), "pipelined": _report(12, 90),
             "meta": {"note": "not a leg"}}
    assert sorted(legs(multi)) == ["legacy", "pipelined"]


def test_compare_records_violations_and_critical_path_diff():
    cp = {"critical_path": {
        "segments": {"decode_compute": {"mean_ms": 10.0},
                     "wire_tx": {"mean_ms": 2.0}},
        "dominant": {"decode_compute": 5},
    }}
    cp2 = {"critical_path": {
        "segments": {"decode_compute": {"mean_ms": 14.0},
                     "wire_tx": {"mean_ms": 1.0}},
        "dominant": {"decode_compute": 3, "wire_tx": 2},
    }}
    old = {"legacy": _report(100, 100, cp)}
    new = {"legacy": _report(90, 120, cp2), "extra": _report(1, 1)}
    rules = (parse_fail_rule("goodput.tok_s=-5%"),
             parse_fail_rule("latency_ms.e2e.p95_ms=+10%"))
    res = compare_records(old, new, rules=rules)
    assert res["ok"] is False and len(res["violations"]) == 2
    assert all(v.startswith("[legacy]") for v in res["violations"])
    assert res["unmatched_new"] == ["extra"]
    leg = res["legs"]["legacy"]
    assert leg["metrics"]["goodput.tok_s"]["delta"] == -10
    assert leg["critical_path_mean_ms"]["decode_compute"]["delta"] == 4.0
    assert leg["dominant"]["wire_tx"]["new"] == 2.0
    with pytest.raises(ValueError):
        compare_records(old, new, leg="extra")  # not present in both
    d = diff_leg(_report(10, 100), _report(10, 100))
    assert all(e["delta"] == 0 for e in d["metrics"].values())


def test_compare_records_record_level_comparison_rules():
    """`comparison.*` rules gate the multi-leg record's own cross-leg
    summary (the fleet record's goodput ratio / failover 5xx count), not
    a per-leg lookup — and a ratio that IMPROVED never trips."""
    old = {"one": _report(50, 100), "two": _report(95, 100),
           "comparison": {"goodput_ratio": 1.9, "failover_http_5xx": 0}}
    new_bad = {"one": _report(50, 100), "two": _report(60, 100),
               "comparison": {"goodput_ratio": 1.2, "failover_http_5xx": 2}}
    rules = (parse_fail_rule("comparison.goodput_ratio=-10%"),
             parse_fail_rule("comparison.failover_http_5xx=+0"))
    res = compare_records(old, new_bad, rules=rules)
    assert len(res["violations"]) == 2
    assert all(v.startswith("[record]") for v in res["violations"])
    new_ok = {"one": _report(50, 100), "two": _report(99, 100),
              "comparison": {"goodput_ratio": 1.98, "failover_http_5xx": 0}}
    assert compare_records(old, new_ok, rules=rules)["ok"] is True


def test_bench_compare_cli_exit_codes(tmp_path, capsys):
    from scripts.bench_compare import main

    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_report(100, 100)))
    new.write_text(json.dumps(_report(98, 104)))
    assert main([str(old), str(new)]) == 0
    out = capsys.readouterr().out
    assert "no gated regressions" in out
    assert main([str(old), str(new), "--fail-on", "goodput.tok_s=-1%",
                 "--json"]) == 1
    res = json.loads(capsys.readouterr().out)
    assert res["ok"] is False
    with pytest.raises(SystemExit):
        main([str(old), str(tmp_path / "missing.json")])
    with pytest.raises(SystemExit):  # argparse usage error on a bad spec
        main([str(old), str(new), "--fail-on", "garbage"])


def test_build_report_carries_critical_path_section():
    """BENCH_SERVE acceptance proxy: loadgen rows that captured a ledger
    aggregate into the report's critical_path section."""
    from dnet_tpu.loadgen import RequestOutcome, WorkloadSpec, build_report

    def row(i, decode, wire):
        segs = {seg: 0.0 for seg in REQUEST_SEGMENTS}
        segs[SEG_DECODE_COMPUTE] = decode
        segs["wire_tx"] = wire
        return RequestOutcome(
            index=i, t_sched_s=10.0, t_start_s=10.0, status=200, ok=True,
            tokens_out=4, ttft_ms=50.0, e2e_ms=decode + wire,
            critical_path={"segments_ms": segs, "total_ms": decode + wire,
                           "e2e_ms": decode + wire, "coverage": 1.0,
                           "dominant": SEG_DECODE_COMPUTE},
        )

    spec = WorkloadSpec(seed=0, requests=2, rate_rps=1.0)
    rep = build_report([row(0, 80.0, 20.0), row(1, 120.0, 40.0)],
                       spec=spec, duration_s=20.0)
    cp = rep["critical_path"]
    assert cp["requests"] == 2
    assert set(cp["segments"]) == set(REQUEST_SEGMENTS)
    assert cp["segments"][SEG_DECODE_COMPUTE]["mean_ms"] == 100.0
    assert cp["segments"]["wire_tx"]["sum_ms"] == 60.0
    assert cp["dominant"] == {SEG_DECODE_COMPUTE: 2}
    assert cp["coverage_mean"] == 1.0
    json.dumps(rep)


# ---- acceptance: in-process two-shard ring --------------------------------


async def _ring_acceptance(model_dir):
    from aiohttp.test_utils import TestClient, TestServer

    from dnet_tpu.loadgen.ring_harness import InprocRing

    get_recorder().clear()
    ring = InprocRing(str(model_dir))
    await ring.start()
    try:
        client = TestClient(TestServer(ring.app))
        await client.start_server()
        try:
            def body(prompt, max_tokens=8):
                return {
                    "model": "inproc-ring",
                    "messages": [{"role": "user", "content": prompt}],
                    "max_tokens": max_tokens,
                    "temperature": 0,
                    "stream": True,
                    "profile": True,
                }

            # warmup absorbs jit compiles so the measured request's wall
            # time is serving time, not tracing time
            warm = await client.post("/v1/chat/completions",
                                     json=body("warm up", 4))
            assert warm.status == 200, await warm.text()
            await warm.read()

            t0 = time.perf_counter()
            resp = await client.post("/v1/chat/completions",
                                     json=body("A quick brown"))
            assert resp.status == 200, await resp.text()
            raw = (await resp.read()).decode()
            e2e_client_ms = (time.perf_counter() - t0) * 1000.0

            chunks = [json.loads(ln[len("data: "):])
                      for ln in raw.splitlines()
                      if ln.startswith("data: ") and ln != "data: [DONE]"]
            assert len(chunks) > 2
            rid = chunks[0]["id"]
            final = chunks[-1]
            ledger = final["metrics"]["critical_path"]

            # --- reconciliation: the ledger partitions the window and the
            # window tracks what the client measured
            segs = ledger["segments_ms"]
            assert set(segs) == set(REQUEST_SEGMENTS)
            assert sum(segs.values()) == pytest.approx(
                ledger["total_ms"], abs=0.05
            )
            # the tiny-fixture request is tens of ms, where HTTP client
            # overhead is a visible fraction — 10% relative with a small
            # absolute floor keeps the contract meaningful without flaking
            diff = abs(ledger["total_ms"] - e2e_client_ms)
            assert diff <= max(0.10 * e2e_client_ms, 20.0), (
                ledger["total_ms"], e2e_client_ms,
            )
            # real ring work was attributed, not dumped into `other`
            assert segs[SEG_OTHER] < ledger["total_ms"]
            assert ledger["spans_attributed"] > 0

            # --- /v1/debug/timeline embeds the same decomposition
            tl = await client.get(f"/v1/debug/timeline/{rid}")
            assert tl.status == 200
            tl_body = await tl.json()
            cp = tl_body["critical_path"]
            assert set(cp["segments_ms"]) == set(REQUEST_SEGMENTS)
            assert sum(cp["segments_ms"].values()) == pytest.approx(
                cp["total_ms"], abs=0.05
            )

            # --- Perfetto export: structurally valid, cross-hop flows
            tr = await client.get(f"/v1/debug/trace/{rid}?format=perfetto")
            assert tr.status == 200
            trace = await tr.json()
            events = trace["traceEvents"]
            assert trace["displayTimeUnit"] == "ms"
            assert {e["ph"] for e in events} & {"M", "X"}
            procs = [e for e in events
                     if e["ph"] == "M" and e["name"] == "process_name"]
            assert {p["args"]["name"] for p in procs} >= {"api"}
            flows_s = [e for e in events if e["ph"] == "s"]
            flows_f = [e for e in events if e["ph"] == "f"]
            # both hops of the ring (api->s0 and s0->s1) arrow at least
            # once per decoded frame
            assert len(flows_s) >= 2
            assert len(flows_s) == len(flows_f)
            assert all(e["id"].startswith(rid) for e in flows_s + flows_f)
            paired = {e["id"] for e in flows_s}
            assert paired == {e["id"] for e in flows_f}
            for e in events:
                assert "pid" in e
                if e["ph"] != "M":
                    assert "ts" in e
            assert tr.headers["Content-Type"].startswith("application/json")

            bad = await client.get(f"/v1/debug/trace/{rid}?format=protobuf")
            assert bad.status == 400
            gone = await client.get("/v1/debug/trace/not-a-rid")
            assert gone.status == 404

            # --- serving-window dump covers the retained timelines
            win = await client.get("/v1/debug/trace?last_s=120")
            assert win.status == 200
            wtrace = await win.json()
            assert wtrace["otherData"]["timelines"] >= 2  # warmup + measured

            # --- /v1/debug/sched responds with the ring snapshot shape
            sc = await client.get("/v1/debug/sched")
            assert sc.status == 200
            snap = await sc.json()
            assert snap["states"] == list(QUEUE_STATES)
            assert {"ticks_captured", "ticks_retained",
                    "capacity"} <= set(snap["summary"])
            assert isinstance(snap["records"], list)
        finally:
            await client.close()
    finally:
        await ring.stop()


@pytest.mark.ring
@pytest.mark.shard
@pytest.mark.http
def test_ring_critical_path_acceptance(tiny_llama_dir):
    """ACCEPTANCE: segment sums reconcile with the client-measured E2E,
    the exported trace carries cross-hop flow events, and the debug
    endpoints serve the new surfaces — through the real HTTP server over
    the in-process two-shard ring."""
    asyncio.run(_ring_acceptance(tiny_llama_dir))


def test_sched_tick_records_agree_with_counters(tiny_llama_dir):
    """The /v1/debug/sched ring and the dnet_sched_* aggregates are two
    views of the same ticks: captured count matches the counter delta and
    the ratio histogram, record by record."""
    from tests.subsystems.test_sched import _serve_burst

    os.environ["DNET_OBS_ENABLED"] = "1"
    reset_settings_cache()
    reset_obs()  # zero counters + empty tick ring: deltas == totals
    try:
        outs = asyncio.run(_serve_burst(
            tiny_llama_dir, ["Hi", "Hello there"], sched=True
        ))
        assert all(outs)
        snap = get_tick_recorder().snapshot()
        captured = snap["summary"]["ticks_captured"]
        assert captured > 0
        assert metric("dnet_sched_tick_records_total").value == captured
        ratio = metric("dnet_sched_tick_budget_used_ratio")
        budgeted = [r for r in snap["records"] if r["budget_tokens"] > 0]
        assert ratio.count == len(budgeted)
        for rec in snap["records"]:
            assert rec["budget_used"] == (
                rec["prefill_tokens"] + rec["decode_lanes"]
            )
            assert rec["budget_wasted"] == max(
                rec["budget_tokens"] - rec["budget_used"], 0
            )
            assert set(rec["queue_depths"]) == set(QUEUE_STATES)
        # the sched tick loop also observed every tick's wall time
        assert metric("dnet_sched_tick_ms").count >= captured
    finally:
        reset_settings_cache()
