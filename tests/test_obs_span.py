"""obs.span: the one host-span primitive (dnet_tpu/obs/__init__.py).

A span is a profiler TraceAnnotation plus ONE dnet_span_ms observation per
exit; names are declared in obs/phases.py HOST_SPANS; nothing is fenced and
no JAX backend is started by opening one.
"""

import subprocess
import sys
import time

import pytest

from dnet_tpu.obs import metric, observe_span, span
from dnet_tpu.obs.phases import DECODE_CHILD_SPANS, HOST_SPANS


def _count(name):
    return metric("dnet_span_ms").labels(span=name).count


def _sum(name):
    return metric("dnet_span_ms").labels(span=name).sum


def test_span_nests_and_observes_once_per_exit():
    before = {n: (_count(n), _sum(n)) for n in ("dnet.tick", "dnet.tick.decode")}
    with span("dnet.tick", decode_lanes=2, prefill_chunks=0) as outer:
        with span("dnet.tick.decode") as inner:
            time.sleep(0.002)
        assert _count("dnet.tick.decode") == before["dnet.tick.decode"][0] + 1
        assert _count("dnet.tick") == before["dnet.tick"][0]  # still open
    assert _count("dnet.tick") == before["dnet.tick"][0] + 1
    # the child's duration lies inside the parent's, on one host clock
    assert 2.0 <= inner.ms <= outer.ms
    assert _sum("dnet.tick") - before["dnet.tick"][1] == pytest.approx(outer.ms)


def test_span_observes_when_the_body_raises():
    n = _count("dnet.sched.plan")
    with pytest.raises(RuntimeError):
        with span("dnet.sched.plan"):
            raise RuntimeError("boom")
    assert _count("dnet.sched.plan") == n + 1


def test_undeclared_span_name_is_refused():
    with pytest.raises(ValueError, match="HOST_SPANS"):
        span("dnet.made_up")
    with pytest.raises(ValueError, match="HOST_SPANS"):
        observe_span("dnet.made_up", 1.0)


def test_observe_span_is_the_histogram_half_alone():
    n, s = _count("dnet.api.sse_flush"), _sum("dnet.api.sse_flush")
    observe_span("dnet.api.sse_flush", 1.5)
    assert _count("dnet.api.sse_flush") == n + 1
    assert _sum("dnet.api.sse_flush") - s == pytest.approx(1.5)


@pytest.mark.parametrize("name", HOST_SPANS)
def test_every_declared_span_is_exposed_from_the_start(name):
    from dnet_tpu.obs import get_registry

    assert f'dnet_span_ms_count{{span="{name}"}}' in get_registry().expose()


def test_decode_children_are_declared_spans():
    assert set(DECODE_CHILD_SPANS) <= set(HOST_SPANS)
    assert all(n.startswith("dnet.decode.") for n in DECODE_CHILD_SPANS)


def test_span_starts_no_jax_backend_and_is_cheap():
    """Opening spans in a fresh process creates no XLA client (a /metrics
    scrape or an idle server must not grab the chips), and a span costs
    microseconds while no profiler session runs."""
    code = (
        "import time\n"
        "from dnet_tpu.obs import span\n"
        "with span('dnet.tick', decode_lanes=1, prefill_chunks=0): pass\n"
        "t = time.perf_counter()\n"
        "for _ in range(2000):\n"
        "    with span('dnet.decode.launch', lanes=1): pass\n"
        "per_us = (time.perf_counter() - t) / 2000 * 1e6\n"
        "from jax._src import xla_bridge\n"
        "print(len(xla_bridge._backends), per_us)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, check=True
    ).stdout.split()
    assert int(out[0]) == 0
    assert float(out[1]) < 200.0  # a handful of microseconds in practice


def test_span_lands_in_a_profile_beside_the_ops(tmp_path):
    """While a profiler session runs, the span is an event of the host
    plane under its own name with its args as stats: what
    benchmarks/harness/xplane.py load() returns under `host`."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    x = jnp.ones((8, 8))
    (x @ x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("dnet.tick", decode_lanes=3, prefill_chunks=1):
            with span("dnet.decode.readback"):
                (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dnet."):
                    found[e.name] = (e.start_ns, e.duration_ns, dict(e.stats))
    assert set(found) == {"dnet.tick", "dnet.decode.readback"}
    t0, dur, stats = found["dnet.tick"]
    assert stats == {"decode_lanes": 3, "prefill_chunks": 1}
    r0, rdur, _ = found["dnet.decode.readback"]
    assert t0 <= r0 and r0 + rdur <= t0 + dur
