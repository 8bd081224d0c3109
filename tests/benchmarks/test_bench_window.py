"""The token-stamped window arithmetic on synthetic streams."""

import pytest

from benchmarks.harness import window

TICK = 1.0


def wave_streams(lanes=32, answer=32, prefill=8.0, t_end=200.0):
    """Lock-step waves (PR 22's traffic): every lane starts together, all
    prefill (no tokens), then `answer` decode ticks together, then again."""
    streams, t = [], 0.0
    while t < t_end:
        first = t + prefill
        for _ in range(lanes):
            streams.append((t, [first + k * TICK for k in range(answer)]))
        t = first + answer * TICK
    return streams


def desync_streams(lanes=32, answer=32, prefill=8.0, t_end=200.0):
    """The same lanes out of phase: lane i starts i/lanes of a period in."""
    period = prefill + answer * TICK
    streams = []
    for lane in range(lanes):
        t = -period * lane / lanes
        while t < t_end:
            first = t + prefill
            streams.append((t, [first + k * TICK for k in range(answer)]))
            t = first + answer * TICK
    return streams


@pytest.mark.parametrize("t0", [30.0, 36.0, 70.0, 76.0])  # windows that straddle a prefill
def test_lock_step_waves_show_large_drift(t0):
    assert window.drift_pct(wave_streams(), t0, t0 + 20.0) > 25.0


@pytest.mark.parametrize("t0", [40.0, 45.0, 50.0, 57.0])
def test_desynchronised_lanes_show_small_drift(t0):
    assert window.drift_pct(desync_streams(), t0, t0 + 20.0) < 5.0


@pytest.mark.parametrize("t0", [40.0, 47.0, 55.0])
def test_desynchronised_rate_does_not_depend_on_where_the_window_falls(t0):
    got = window.summarize(desync_streams(), t0, t0 + 20.0)["output_tokens_per_s"]
    assert got == pytest.approx(32 * 32 / 40.0, rel=0.03)


def test_requests_in_flight_contribute_their_tokens():
    # one stream starts before the window and ends after it: nothing completes
    s = [(0.0, [float(t) for t in range(5, 100)])]
    assert window.tokens_in(s, 10.0, 20.0) == 10
    out = window.summarize(s, 10.0, 20.0)
    assert out["output_tokens_per_s"] == pytest.approx(1.0)
    assert out["n_gaps"] == 10 and out["n_ttft"] == 0


def test_gap_counts_where_its_later_token_falls():
    s = [(0.0, [9.0, 10.5, 19.9, 20.1])]
    assert window.gaps_in(s, 10.0, 20.0) == pytest.approx([1.5, 9.4])


def test_ttft_is_timed_from_due_time_and_placed_by_first_token():
    s = [(2.0, [11.0, 12.0]), (9.5, [25.0]), (1.0, [3.0, 11.5])]
    assert window.ttfts_in(s, 10.0, 20.0) == pytest.approx([9.0])
    assert window.summarize(s, 10.0, 20.0)["ttft_p50_ms"] == pytest.approx(9000.0)


@pytest.mark.parametrize("n,has_p95", [(199, False), (200, True), (1400, True)])
def test_p95_only_where_the_sample_supports_it(n, has_p95):
    s = [(0.0, [0.01 * k for k in range(n + 1)])]
    out = window.summarize(s, 0.001, 1e9)
    assert out["n_gaps"] == n
    assert ("itl_p95_ms" in out) is has_p95
    assert out["itl_p50_ms"] == pytest.approx(10.0)


@pytest.mark.parametrize("q,want", [(0.5, 2.0), (0.95, 4.0), (0.0, 1.0), (1.0, 4.0)])
def test_nearest_rank(q, want):
    assert window.nearest_rank([4.0, 1.0, 3.0, 2.0], q) == want


def test_empty_window_reads_zero_not_an_error():
    out = window.summarize([], 0.0, 1.0)
    assert out["tokens"] == 0 and out["window_drift_pct"] == 0.0
    assert "itl_p50_ms" not in out and "ttft_p50_ms" not in out


def burst_streams(offset):
    """32 lanes, one token per lane per tick, ticks 0.5 s apart, each burst
    spread over 20 ms; the whole timeline shifted by `offset`."""
    return [(0.0, [offset + 0.5 * k + 0.02 * lane / 32 for k in range(1, 200)])
            for lane in range(32)]


@pytest.mark.parametrize("offset", [0.0, 0.004, 0.013, 0.021, 0.047])
def test_the_window_is_the_runs_own_whatever_the_bursts_do(offset):
    """A fixed window counts what arrived inside it: an edge inside a burst
    counts part of the burst, and the rate is still within a burst of the
    truth.  Nothing moves the edges to suit the tokens."""
    s = burst_streams(offset)
    t0, t1 = 10.012, 58.012
    got = window.summarize(s, t0, t1)
    assert abs(got["tokens"] - 96 * 32) <= 32
    assert got["output_tokens_per_s"] == got["tokens"] / 48.0


def test_a_stall_still_running_at_the_windows_end_lowers_the_rate():
    """Tokens stop 6 s before the window closes and resume after it: the
    window's length is not cut to the last burst, so the rate falls."""
    steady = [(0.0, [0.5 * k for k in range(1, 200)])]
    stalled = [(0.0, [0.5 * k for k in range(1, 200) if not 42.0 < 0.5 * k <= 50.0])]
    a = window.summarize(steady, 0.0, 48.0)["output_tokens_per_s"]
    b = window.summarize(stalled, 0.0, 48.0)["output_tokens_per_s"]
    assert a == pytest.approx(2.0) and b == pytest.approx(2.0 * 42 / 48)
    # the gap that ends after the window is not one of the window's gaps
    assert max(window.gaps_in(stalled, 0.0, 48.0)) == pytest.approx(0.5)
