"""The turn-around metrics (PR 39): twelve `layer_metrics/*.json`, all
`prom_delta`, each read through `readers.read` from a recorded scrape pair;
BENCHMARK.json with their twelve entries keeps the contract's rules; the
parent's program (PR 38) gives nothing for the eight whose span or family
it lacks and does not raise.  PR 43: the mean over the DRAINED turn-arounds
went (PR 40 keeps a step in flight, so there are none and it read `null`)
and the mean over the BUSY ones stands in its place, same family.

The pairs under harness/testdata/ are the `dnet_span_ms` and `dnet_sched_*`
families of real expositions (the scheduler over the tiny llama on the CPU,
before and after three streams; no bucket lines): `metrics_turn_*` of this
PR's program with the series the readers read set to round numbers,
`metrics_pr38_*` of the parent's as it was.
"""

import pytest

from benchmarks.harness import prom, readers, spec

DATA = spec.BENCH_DIR / "harness" / "testdata"
CELLS = [
    "qwen3moe-ragprompt-sat", "cmdaplus-mixedlen-sat",
    "brumby-longgen-sat", "qwen3next-longdoc-sat",
]
SCHED, PROGRAMS, API = "scheduler", "engine programs", "HTTP + admission"
TOKENS, TTFT = "output_tokens_per_s", "ttft_p50_ms"
BUSY_SUM_MS = 450.0 - 50.0  # dnet_sched_turnaround_ms_sum{device="busy"} over the recorded window

# metric -> (value over the recorded window, unit, source, layer, moves)
WANT = {
    "turnaround_busy_mean_ms": (BUSY_SUM_MS / 100, "ms", "program_span", SCHED, TOKENS),
    "turnarounds_drained_in_window": (900, "ticks", "program_counter", SCHED, TOKENS),
    "turn_to_loop_mean_ms": (410 / 1025, "ms", "program_span", SCHED, TOKENS),
    "turn_to_thread_mean_ms": (205 / 1025, "ms", "program_span", SCHED, TOKENS),
    "sched_apply_mean_ms": (615 / 1025, "ms", "program_span", SCHED, TOKENS),
    "sched_plan_mean_ms": (330 / 1100, "ms", "program_span", SCHED, TOKENS),
    "drivers_turn_mean_ms": (4500 / 900, "ms", "program_span", SCHED, TOKENS),
    "decode_launch_mean_ms": (2050 / 1025, "ms", "program_span", PROGRAMS, TOKENS),
    "decode_lanes_left_out_in_window": (4500, "lanes", "program_counter", SCHED, TOKENS),
    "drivers_turns_timed_out_in_window": (720, "turns", "program_counter", SCHED, TOKENS),
    "driver_answer_wait_mean_ms": (31000 / 10000, "ms", "program_span", API, TOKENS),
    "prefill_readback_wait_mean_ms": (8400 / 70, "ms", "program_span", PROGRAMS, TTFT),
}
# spans the parent's program already had (PR 24 made them, nothing read them)
PARENT_HAS = {
    "sched_apply_mean_ms", "sched_plan_mean_ms", "decode_launch_mean_ms",
    "prefill_readback_wait_mean_ms",
}
# A metric lists the cells in which its reader finds something to read: all
# twelve read in PR 39's four cells (a drained count of 0 is a reading), and
# cells added since may have joined.


def evidence(scrapes):
    return readers.Evidence(client={}, scrapes=scrapes, trace=None, memory={})


def recorded(which):
    return [prom.parse((DATA / f"metrics_{which}_{w}.txt").read_text()) for w in ("before", "after")]


def test_there_are_twelve():
    assert len(WANT) == 12 and PARENT_HAS < set(WANT)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_each_reader_reads_its_value_from_the_recorded_pair(metric):
    reader = spec.load_json(spec.layer_metric_file(metric))
    assert reader["reader"] == "prom_delta" and reader["what"]
    assert reader["reader"] in readers.READERS
    assert readers.read(reader, evidence(recorded("turn"))) == pytest.approx(WANT[metric][0])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_the_parents_program_gives_nothing_where_it_lacks_the_series(metric):
    """The driver lays this PR's benchmark files over the parent's checkout
    too: a reader of a span or family PR 38 does not have returns None and
    the line leaves the metric out; the four spans it has read as they do
    here."""
    reader = spec.load_json(spec.layer_metric_file(metric))
    value = readers.read(reader, evidence(recorded("pr38")))
    if metric in PARENT_HAS:
        assert value is not None and value > 0.0
    else:
        assert value is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_one_scrape_alone_gives_nothing(metric):
    reader = spec.load_json(spec.layer_metric_file(metric))
    assert readers.read(reader, evidence(recorded("turn")[:1])) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_each_entry_lists_its_cells_and_names_its_layer(metric):
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    _, unit, source, layer, moves = WANT[metric]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": metric, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": moves,
    }
    assert set(CELLS) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert metric in {m["name"] for m in spec.resolve_cell(cell).per_layer}


def test_a_mean_over_no_observation_is_left_out_of_the_line():
    """The family is there, the child never moved: a mean reads None (the
    line leaves it out, which is why the drained mean went), a count 0."""
    before, _ = recorded("turn")
    mean = spec.load_json(spec.layer_metric_file("turnaround_busy_mean_ms"))
    count = spec.load_json(spec.layer_metric_file("turnarounds_drained_in_window"))
    assert readers.read(mean, evidence([before, before])) is None
    assert readers.read(count, evidence([before, before])) == 0.0


def test_the_extended_benchmark_keeps_the_contracts_rules():
    bench = spec.load_benchmark()
    assert spec.validate(bench) == []
    assert set(CELLS) <= {w["name"] for w in bench["workloads"]}  # at least PR 39's four
    # the layers are ones the benchmark already named, letter for letter
    older = {m["layer"] for m in bench["per_layer"] if m["name"] not in WANT}
    assert {SCHED, PROGRAMS, API} <= older


def test_the_busy_mean_leaves_the_drained_turnarounds_out():
    before, after = recorded("turn")
    fam = "dnet_sched_turnaround_ms"
    both = prom.delta(after, before, fam + "_sum") / prom.delta(after, before, fam + "_count")
    assert both != pytest.approx(WANT["turnaround_busy_mean_ms"][0])
    busy = prom.delta(after, before, fam + "_count", {"device": "busy"})
    assert busy == 100 and WANT["turnarounds_drained_in_window"][0] == 900
    drained = prom.delta(after, before, fam + "_sum", {"device": "drained"})
    assert drained / 900 == pytest.approx(7920 / 900)  # what the retired mean read
    assert prom.delta(after, before, fam + "_sum", {"device": "busy"}) == pytest.approx(BUSY_SUM_MS)


def test_the_timed_out_count_is_one_outcome_of_three():
    before, after = recorded("turn")
    fam = "dnet_sched_drivers_turn_total"
    by = {o: prom.delta(after, before, fam, {"outcome": o}) for o in ("answered", "timed_out", "none")}
    assert by["timed_out"] == WANT["drivers_turns_timed_out_in_window"][0]
    assert sum(by.values()) == prom.delta(after, before, fam) == 1025


def test_the_readers_table_is_as_it_was():
    assert sorted(readers.READERS) == [
        "client", "device_memory", "prom_delta", "trace_idle", "trace_share",
    ]
