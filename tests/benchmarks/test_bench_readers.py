"""The reductions: Prometheus deltas against a golden exposition, and the
xplane reduction against a synthetic nested trace and one small trace
recorded on the v5e (benchmarks/harness/testdata/)."""

import pytest

from benchmarks.harness import prom, readers, spec, xplane

DATA = spec.BENCH_DIR / "harness" / "testdata"
# what a four-chip cell's collective_time_pct would match (PERF.md section 7)
COLLECTIVES = r"^%?(all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter)"


def scrapes():
    return [prom.parse((DATA / f"metrics_{w}.txt").read_text())
            for w in ("before", "mid", "after")]


def evidence(**kw):
    base = dict(client={}, scrapes=[], trace=None, memory={})
    base.update(kw)
    return readers.Evidence(**base)


def test_parser_keeps_labels_and_skips_what_is_not_a_sample():
    s = scrapes()[2]
    assert s['dnet_jit_compiles_total{fn="prefill"}'] == 13
    assert s['dnet_sched_tick_ms_bucket{le="+Inf"}'] == 30
    assert "dnet_broken_value" not in s and len(s) == 14


@pytest.mark.parametrize(
    "metric,want",
    [
        ("sched_tick_host_mean_ms", 2000.0),
        ("sched_batch_tokens_mean", (7680 + 940 - 2560 - 300) / 40),
        ("compiles_in_window", 1.0),
        ("kv_blocks_used_peak_pct", 1024 / 8192 * 100),
        ("admit_wait_mean_ms", 30.0 / 20),
    ],
)
def test_prom_delta_metrics_against_the_golden_exposition(metric, want):
    reader = spec.load_json(spec.layer_metric_file(metric))
    assert readers.read(reader, evidence(scrapes=scrapes())) == pytest.approx(want)


def test_label_filter_and_missing_family():
    ev = evidence(scrapes=scrapes())
    one = {"reader": "prom_delta", "family": "dnet_jit_compiles_total",
           "labels": {"fn": "batched_step"}, "stat": "sum"}
    assert readers.read(one, ev) == 0.0
    absent = {"reader": "prom_delta", "family": "dnet_no_such_family", "stat": "mean"}
    assert readers.read(absent, ev) is None  # nothing to read: left out of the line
    assert readers.read({"reader": "trace_idle"}, ev) is None
    assert readers.read({"reader": "client", "field": "itl_p50_ms"}, ev) is None


def nested_trace():
    """A while of 100 ns enclosing a sort (30) and a custom call (20), then
    a gap of 50, then a fusion (50); a second device busy throughout."""
    return {
        "devices": {
            "/device:TPU:0": [
                ["%while.1 = (s32[]) while(...)", 0, 100],
                ["%sort.7 = (f32[32,1,151936]) sort(...)", 10, 30],
                ["%paged_attend.8 = bf16[32,4,8,128] custom-call(...), custom_call_target=\"tpu_custom_call\"", 50, 20],
                ["%fusion.3 = bf16[8] fusion(...), calls=%fused_sort_like", 150, 50],
            ],
            "/device:TPU:1": [["%all-reduce.2 = bf16[8] all-reduce(...)", 0, 200]],
        },
        "host": [["outer", 0, 1000], ["np.asarray(jax.Array)", 95, 60], ["elsewhere", 500, 10]],
    }


def test_self_times_attribute_nested_time_once():
    t = nested_trace()
    got = dict(xplane.self_times(t["devices"]["/device:TPU:0"]))
    assert got["%while.1 = (s32[]) while(...)"] == 50
    assert sum(got.values()) == 150  # = busy time of the device
    assert xplane.busy_s(t) == pytest.approx((150 + 200) / 2 / 1e9)
    assert xplane.window_s(t) == pytest.approx(200e-9)
    assert xplane.idle_pct(t) == pytest.approx(12.5)


def test_trace_share_patterns_of_the_shipped_metrics():
    ev = evidence(trace=nested_trace())

    def share(name):
        return readers.read(spec.load_json(spec.layer_metric_file(name)), ev)

    assert share("sort_time_pct.ttft") == pytest.approx(30 / 150 * 100 / 2)  # not the fusion
    assert share("pallas_time_pct.attn") == pytest.approx(20 / 150 * 100 / 2)
    assert share("device_idle_pct") == pytest.approx(12.5)
    collectives = {"reader": "trace_share", "pattern": COLLECTIVES, "of": "busy"}
    assert readers.read(collectives, ev) == pytest.approx(100 / 2)  # mean over devices


def test_breakdown_names_ops_by_self_time_and_gaps_by_host_span():
    b = xplane.breakdown(nested_trace())
    assert [n.split(" ")[0] for n, _ in b["device_ops"][:2]] == ["%all-reduce.2", "%while.1"]
    assert b["device_ops"][0][1] == pytest.approx(100e-9)  # 200 ns over 2 devices
    assert b["idle_gaps"] == [["np.asarray(jax.Array)", pytest.approx(50e-9)]]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_recorded_v5e_trace():
    """0.25 s of a traced slice of this configuration's decode ticks (32
    clients, answers 32-96), recorded on the chip (my chip run, PR 23).  Pins the reduction: a change that moves these numbers
    changes the yardstick."""
    t = xplane.load_recorded(DATA / "v5e_qwen3moe_chat_sat.trace.json.gz")
    assert list(t["devices"]) == ["/device:TPU:0"]
    ops = t["devices"]["/device:TPU:0"]
    selfs = xplane.self_times(ops)
    busy = xplane.union_ns([(s, s + d) for _, s, d in ops])
    assert sum(d for _, d in selfs) == pytest.approx(busy, rel=1e-6)
    assert 0.0 <= xplane.idle_pct(t) < 100.0
    shares = {p.split(".")[0]: xplane.share_pct(t, spec.load_json(spec.layer_metric_file(p))["pattern"])
              for p in ("sort_time_pct.ttft", "pallas_time_pct.attn")}
    assert xplane.share_pct(t, COLLECTIVES) == 0.0  # one chip
    assert shares["sort_time_pct"] > 0 and shares["pallas_time_pct"] > 0
    assert sum(shares.values()) < 100.0
    golden = spec.load_json(DATA / "v5e_qwen3moe_chat_sat.golden.json")
    assert xplane.idle_pct(t) == pytest.approx(golden["idle_pct"], rel=1e-9)
    for k, v in shares.items():
        assert v == pytest.approx(golden[k], rel=1e-9)
    b = xplane.breakdown(t)
    assert [n for n, _ in b["device_ops"]] == golden["top_ops"]


def test_load_reads_a_real_xplane_file(tmp_path):
    """The loader on a profile of this machine (CPU: host spans, no device
    plane, so every trace reader returns nothing)."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = xplane.load(xplane.find_xplane(tmp_path))
    assert t["devices"] == {} and len(t["host"]) > 0
    assert readers.read({"reader": "trace_idle"}, evidence(trace=t)) is None
    with pytest.raises(FileNotFoundError):
        xplane.find_xplane(tmp_path / "nothing")
