"""Tier-1 hook + fixture suite for the static-analysis framework
(dnet_tpu/analysis/, CLI scripts/dnetlint.py).

Four layers:

1. **Per-check fixtures** — for every AST check DL001-DL009 and the
   flow-sensitive tier DL021-DL025, a known-bad snippet must fire with
   the right code and line, and a known-good snippet must stay quiet.
   Fixtures run through the same ``analyze_texts`` entry the full runner
   uses (suppressions applied, runtime checks excluded).
2. **CFG / dataflow mechanics** — branch join, loop back-edge, and
   try/except edges in the flow tier's graphs and solvers
   (dnet_tpu/analysis/flow/).
3. **Framework mechanics** — suppression syntax (trailing, standalone,
   reason-mandatory), baseline round trip (write -> rerun clean -> stale
   entry fails), deterministic finding order, ``--select`` validation,
   ``--diff`` incremental mode.
4. **Self-run wrapper** — ``python scripts/dnetlint.py --json`` over THIS
   repo must exit 0 (empty-or-justified baseline is an acceptance
   criterion), which also folds the metric passes (DL010+) into tier-1 —
   plus seeded negative controls that inject one violation into the real
   hot files and demand exactly the expected DL021/DL022/DL023 finding.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.core

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "scripts" / "dnetlint.py"

sys.path.insert(0, str(REPO)) if str(REPO) not in sys.path else None

from dnet_tpu.analysis import (  # noqa: E402
    ALL_CHECKS,
    Project,
    SourceFile,
    analyze_texts,
    load_baseline,
    write_baseline,
)
from dnet_tpu.analysis.core import run_checks  # noqa: E402

SERVING = "dnet_tpu/api/fixture_mod.py"  # a rel path on the serving scope


def findings_for(text: str, rel: str = SERVING, extra: dict = None):
    texts = {rel: text}
    texts.update(extra or {})
    return analyze_texts(texts)


def codes(fs):
    return [f.code for f in fs]


# ---- DL001 blocking call in async ----------------------------------------


def test_dl001_fires_on_blocking_call():
    fs = findings_for(
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)\n"
    )
    assert codes(fs) == ["DL001"] and fs[0].line == 3


def test_dl001_fires_on_subprocess():
    fs = findings_for(
        "import subprocess\n"
        "async def handler():\n"
        "    subprocess.run(['ls'])\n"
    )
    assert codes(fs) == ["DL001"]


def test_dl001_quiet_on_async_sleep_and_sync_def():
    fs = findings_for(
        "import asyncio, time\n"
        "async def handler():\n"
        "    await asyncio.sleep(1)\n"
        "def sync_helper():\n"
        "    time.sleep(1)\n"  # fine: not on the event loop
    )
    assert fs == []


def test_dl001_quiet_off_serving_path():
    fs = findings_for(
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)\n",
        rel="dnet_tpu/cli/fixture_mod.py",
    )
    assert fs == []


def test_dl001_ignores_nested_sync_def():
    # a nested sync def is typically shipped to an executor; its body is
    # the nested scope's business
    fs = findings_for(
        "import time\n"
        "async def handler(loop):\n"
        "    def work():\n"
        "        time.sleep(1)\n"
        "    await loop.run_in_executor(None, work)\n"
    )
    assert fs == []


# ---- DL002 lock held across await ----------------------------------------


def test_dl002_fires_on_sync_lock_across_await():
    fs = findings_for(
        "async def handler(self):\n"
        "    with self._lock:\n"
        "        await self.flush()\n"
        "async def flush(self):\n"
        "    pass\n"
    )
    assert "DL002" in codes(fs)
    assert [f.line for f in fs if f.code == "DL002"] == [3]


def test_dl002_fires_on_async_lock_across_sleep():
    fs = findings_for(
        "import asyncio\n"
        "async def handler(self):\n"
        "    async with self._lock:\n"
        "        await asyncio.sleep(5)\n"
    )
    assert codes(fs) == ["DL002"]


def test_dl002_quiet_on_async_lock_plain_critical_section():
    fs = findings_for(
        "async def handler(self):\n"
        "    async with self._lock:\n"
        "        self.n += 1\n"
        "    with self._lock:\n"
        "        self.m += 1\n"  # no await inside: fine
    )
    assert fs == []


# ---- DL003 dropped coroutine / task --------------------------------------


def test_dl003_fires_on_dropped_create_task():
    fs = findings_for(
        "import asyncio\n"
        "async def handler():\n"
        "    asyncio.create_task(work())\n"
        "async def work():\n"
        "    pass\n"
    )
    assert codes(fs) == ["DL003"] and fs[0].line == 3


def test_dl003_fires_on_unawaited_local_coroutine():
    fs = findings_for(
        "async def work():\n"
        "    pass\n"
        "async def handler():\n"
        "    work()\n"
    )
    assert codes(fs) == ["DL003"] and fs[0].line == 4


def test_dl003_fires_on_underscore_assignment():
    fs = findings_for(
        "import asyncio\n"
        "async def handler():\n"
        "    _ = asyncio.ensure_future(work())\n"
        "async def work():\n"
        "    pass\n"
    )
    assert codes(fs) == ["DL003"]


def test_dl003_quiet_on_retained_task_and_awaited_coroutine():
    fs = findings_for(
        "import asyncio\n"
        "async def handler(self):\n"
        "    self._task = asyncio.create_task(work())\n"
        "    tasks = [asyncio.ensure_future(work())]\n"
        "    await work()\n"
        "    await asyncio.gather(*tasks)\n"
        "async def work():\n"
        "    pass\n"
    )
    assert fs == []


# ---- DL004 JIT purity ----------------------------------------------------


def test_dl004_fires_on_time_in_jitted_fn():
    fs = findings_for(
        "import time, jax\n"
        "def step(x):\n"
        "    t0 = time.perf_counter()\n"
        "    return x * t0\n"
        "step_fn = jax.jit(step)\n",
        rel="dnet_tpu/ops/fixture_mod.py",  # DL004 is repo-global
    )
    assert codes(fs) == ["DL004"] and fs[0].line == 3


def test_dl004_fires_transitively_and_on_decorator():
    fs = findings_for(
        "import os, jax, functools\n"
        "def helper(x):\n"
        "    return x if os.environ.get('FLAG') else -x\n"
        "@functools.partial(jax.jit, static_argnums=(1,))\n"
        "def step(x, n):\n"
        "    return helper(x) * n\n"
    )
    assert codes(fs) == ["DL004"] and fs[0].line == 3


def test_dl004_fires_on_metrics_observer_in_traced_code():
    fs = findings_for(
        "import jax\n"
        "def step(x):\n"
        "    metric('dnet_foo').inc()\n"
        "    return x\n"
        "fn = jax.jit(step)\n"
    )
    assert codes(fs) == ["DL004"]


def test_dl004_quiet_on_pure_jit_and_untraced_impurity():
    fs = findings_for(
        "import time, jax\n"
        "import jax.numpy as jnp\n"
        "def step(x):\n"
        "    return jnp.tanh(x) * jax.random.normal(jax.random.PRNGKey(0))\n"
        "fn = jax.jit(step)\n"
        "def driver(x):\n"
        "    t0 = time.perf_counter()\n"  # outside the traced graph: fine
        "    return fn(x), time.perf_counter() - t0\n"
    )
    assert fs == []


# ---- DL005 ungated device sync -------------------------------------------


def test_dl005_fires_on_ungated_sync():
    fs = findings_for(
        "import jax\n"
        "def decode_step(x):\n"
        "    jax.block_until_ready(x)\n"
        "    return x.item()\n"
    )
    assert codes(fs) == ["DL005", "DL005"]
    assert [f.line for f in fs] == [3, 4]


def test_dl005_quiet_under_obs_gate():
    fs = findings_for(
        "import jax\n"
        "from dnet_tpu.obs import obs_enabled\n"
        "def decode_step(self, x):\n"
        "    if obs_enabled():\n"
        "        jax.block_until_ready(x)\n"
        "    if self._sync_every_n:\n"
        "        x.block_until_ready()\n"
        "    return x\n"
    )
    assert fs == []


def test_dl005_async_is_not_a_sync_gate():
    """Regression: the gate regex must not match 'sync' inside 'async' —
    an async-heavy codebase would silently exempt itself."""
    fs = findings_for(
        "import jax\n"
        "def dispatch_async(self, x):\n"
        "    jax.block_until_ready(x)\n"
        "    if self.use_async:\n"
        "        x.item()\n"
        "    return x\n"
    )
    assert codes(fs) == ["DL005", "DL005"]


def test_dl005_quiet_off_serving_path():
    fs = findings_for(
        "import jax\n"
        "def probe(x):\n"
        "    jax.block_until_ready(x)\n",
        rel="dnet_tpu/parallel/fixture_mod.py",
    )
    assert fs == []


# ---- DL006 env read outside config ---------------------------------------


def test_dl006_fires_on_raw_dnet_env_read():
    fs = findings_for(
        "import os\n"
        "FLAG = os.environ.get('DNET_MY_FLAG', '0')\n"
        "OTHER = os.getenv('DNET_OTHER')\n"
        "THIRD = os.environ['DNET_THIRD']\n"
        "HAS = 'DNET_FOURTH' in os.environ\n"
    )
    assert codes(fs) == ["DL006"] * 4
    assert [f.line for f in fs] == [2, 3, 4, 5]


def test_dl006_quiet_on_non_dnet_and_allowlisted():
    fs = findings_for(
        "import os\n"
        "P = os.environ.get('JAX_PLATFORMS')\n"  # not a DNET_ var
    )
    assert fs == []
    fs = findings_for(
        "import os\n"
        "V = os.environ.get('DNET_ANYTHING')\n",
        rel="dnet_tpu/config.py",  # the sanctioned reader
    )
    assert fs == []


# ---- DL007 silent exception swallow --------------------------------------


def test_dl007_fires_on_silent_swallow():
    fs = findings_for(
        "async def handler():\n"
        "    try:\n"
        "        await work()\n"
        "    except Exception:\n"
        "        pass\n"
        "async def work():\n"
        "    pass\n"
    )
    assert codes(fs) == ["DL007"] and fs[0].line == 4


def test_dl007_fires_on_bare_except():
    fs = findings_for(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        pass\n"
    )
    assert codes(fs) == ["DL007"]


def test_dl007_quiet_on_logged_or_narrow():
    fs = findings_for(
        "def f(log):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as exc:\n"
        "        log.debug('g failed: %s', exc)\n"
        "    try:\n"
        "        g()\n"
        "    except KeyError:\n"  # narrow: a deliberate contract
        "        pass\n"
    )
    assert fs == []


# ---- DL008 typed errors + frame headers ----------------------------------

_INFERENCE = (
    "class InferenceError(Exception):\n"
    "    pass\n"
    "class MappedError(InferenceError):\n"
    "    pass\n"
    "class UnmappedError(InferenceError):\n"
    "    pass\n"
)
_HTTP_MAPPED = (
    "from dnet_tpu.api.inference import MappedError, UnmappedError\n"
    "def status_for(exc):\n"
    "    if isinstance(exc, MappedError):\n"
    "        return 429\n"
    "    if isinstance(exc, UnmappedError):\n"
    "        return 504\n"
    "    return 500\n"
)
_HTTP_PARTIAL = (
    "from dnet_tpu.api.inference import MappedError\n"
    "def status_for(exc):\n"
    "    if isinstance(exc, MappedError):\n"
    "        return 429\n"
    "    return 500\n"
)


def test_dl008_fires_on_unmapped_typed_error():
    fs = analyze_texts({
        "dnet_tpu/api/inference.py": _INFERENCE,
        "dnet_tpu/api/http.py": _HTTP_PARTIAL,
    })
    assert codes(fs) == ["DL008"]
    assert "UnmappedError" in fs[0].message and fs[0].line == 5


def test_dl008_quiet_when_all_errors_mapped():
    fs = analyze_texts({
        "dnet_tpu/api/inference.py": _INFERENCE,
        "dnet_tpu/api/http.py": _HTTP_MAPPED,
    })
    assert fs == []


def test_dl008_fires_on_unstamped_frame():
    fs = findings_for(
        "from dnet_tpu.transport.protocol import ActivationFrame, TokenPayload\n"
        "def send(nonce):\n"
        "    f = ActivationFrame(nonce=nonce, seq=0)\n"
        "    t = TokenPayload(nonce=nonce, step=0, token_id=1)\n"
        "    return f, t\n"
    )
    assert codes(fs) == ["DL008", "DL008"]
    assert "epoch/deadline" in fs[0].message and fs[0].line == 3
    assert "epoch" in fs[1].message and fs[1].line == 4


def test_dl008_quiet_on_stamped_frame_and_protocol_module():
    fs = findings_for(
        "from dnet_tpu.transport.protocol import ActivationFrame, TokenPayload\n"
        "def send(nonce, dl, ep):\n"
        "    f = ActivationFrame(nonce=nonce, seq=0, deadline=dl, epoch=ep)\n"
        "    t = TokenPayload(nonce=nonce, step=0, token_id=1, epoch=ep)\n"
        "    return f, t\n"
    )
    assert fs == []
    fs = findings_for(
        "def clone(self):\n"
        "    return ActivationFrame(nonce=self.nonce, seq=self.seq)\n",
        rel="dnet_tpu/transport/protocol.py",
    )
    assert fs == []


# ---- DL029 logging hygiene ------------------------------------------------


def test_dl029_fires_on_raw_getlogger():
    fs = findings_for(
        "import logging\n"
        "log = logging.getLogger('dnet')\n"
    )
    assert codes(fs) == ["DL029"]
    assert fs[0].line == 2
    # repo-wide rule: fires off the serving path too (the ops/ drift)
    fs = findings_for(
        "import logging\n"
        "logging.getLogger('x').warning('%s', 1)\n",
        rel="dnet_tpu/ops/fixture_mod.py",
    )
    assert codes(fs) == ["DL029"]


def test_dl029_fires_on_eager_interpolation():
    fs = findings_for(
        "from dnet_tpu.utils.logger import get_logger\n"
        "log = get_logger()\n"
        "def f(rid):\n"
        "    log.info(f'sent {rid}')\n"
        "    log.warning('sent {}'.format(rid))\n"
        "    log.error('sent %s' % rid)\n"
    )
    assert codes(fs) == ["DL029", "DL029", "DL029"]
    assert [f.line for f in fs] == [4, 5, 6]


def test_dl029_quiet_on_lazy_args_allowlist_and_nonserving():
    fs = findings_for(
        "from dnet_tpu.utils.logger import get_logger\n"
        "log = get_logger()\n"
        "def f(rid, exc):\n"
        "    log.info('sent %s', rid)\n"
        "    log.exception('compute failed for %s', rid)\n"
        "    get_logger().warning('probe failed (%s)', exc)\n"
    )
    assert fs == []
    # the logger tree owners may call logging.getLogger
    fs = findings_for(
        "import logging\n"
        "logger = logging.getLogger('dnet_tpu')\n",
        rel="dnet_tpu/utils/logger.py",
    )
    assert fs == []
    # eager interpolation off the serving path is tolerated (CLI glue)
    fs = findings_for(
        "from dnet_tpu.utils.logger import get_logger\n"
        "log = get_logger()\n"
        "def f(x):\n"
        "    log.info(f'loaded {x}')\n",
        rel="dnet_tpu/cli/fixture_mod.py",
    )
    assert fs == []


# ---- DL009 ownership-registry drift + bridge discipline -------------------

_DOMAINS_REL = "dnet_tpu/analysis/runtime/domains.py"


def test_dl009_fires_on_adhoc_thread_loop_bridge():
    fs = findings_for(
        "def feed(loop, q, tok):\n"
        "    loop.call_soon_threadsafe(q.put_nowait, tok)\n"
    )
    assert codes(fs) == ["DL009"] and fs[0].line == 2
    assert "sanctioned bridge modules" in fs[0].message


def test_dl009_quiet_inside_sanctioned_bridge():
    fs = findings_for(
        "def feed(loop, q, tok):\n"
        "    loop.call_soon_threadsafe(q.put_nowait, tok)\n",
        rel="dnet_tpu/shard/runtime.py",
    )
    assert fs == []


def test_dl009_registry_half_runs_only_when_registry_ships():
    from dnet_tpu.analysis.runtime.domains import OWNERSHIP_DOMAINS

    # a tree without the registry file has nothing to drift from
    assert analyze_texts({"dnet_tpu/api/other_mod.py": "X = 1\n"}) == []
    # with it present, every declared module must exist in the tree
    fs = analyze_texts({_DOMAINS_REL: "# the registry ships here\n"})
    assert codes(fs) == ["DL009"] * len(OWNERSHIP_DOMAINS)
    assert all(f.path == _DOMAINS_REL for f in fs)
    assert "missing module" in fs[0].message


def test_dl009_fires_on_missing_attribute_and_lock():
    # ShardRuntime without recv_q (declared thread-owned) and without
    # _model_lock (declared guard of .epoch): both drift findings fire
    fake = (
        "class ShardRuntime:\n"
        "    def __init__(self):\n"
        "        self.out_q = None\n"
        "        self.epoch = 0\n"
        "        self._pending_errs = set()\n"
    )
    fs = analyze_texts({_DOMAINS_REL: "\n", "dnet_tpu/shard/runtime.py": fake})
    mine = [f for f in fs if f.path == "dnet_tpu/shard/runtime.py"]
    assert len(mine) == 2
    msgs = sorted(f.message for f in mine)
    assert "guarded-by(_model_lock)" in msgs[0]
    assert "missing attribute ShardRuntime.recv_q" in msgs[1]


def test_dl009_quiet_when_declarations_match():
    fake = (
        "class ShardRuntime:\n"
        "    def __init__(self):\n"
        "        self.recv_q = None\n"
        "        self.out_q = None\n"
        "        self.epoch = 0\n"
        "        self._pending_errs = set()\n"
        "        self._model_lock = None\n"
    )
    fs = analyze_texts({_DOMAINS_REL: "\n", "dnet_tpu/shard/runtime.py": fake})
    assert [f for f in fs if f.path == "dnet_tpu/shard/runtime.py"] == []


# ---- suppression syntax ---------------------------------------------------


def test_suppression_trailing_and_standalone():
    fs = findings_for(
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)  # dnetlint: disable=DL001 startup settle, loop not serving yet\n"
        "    # dnetlint: disable=DL001 second documented exception\n"
        "    time.sleep(2)\n"
    )
    assert fs == []


def test_suppression_requires_reason():
    fs = findings_for(
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)  # dnetlint: disable=DL001\n"
    )
    # the finding survives AND the bare suppression is itself flagged
    assert sorted(codes(fs)) == ["DL000", "DL001"]


def test_suppression_is_code_scoped():
    fs = findings_for(
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)  # dnetlint: disable=DL007 wrong code on purpose\n"
    )
    assert codes(fs) == ["DL001"]


# ---- baseline round trip --------------------------------------------------


def test_baseline_round_trip(tmp_path):
    bad = (
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)\n"
    )
    project = Project([SourceFile(SERVING, bad)])
    ast_checks = [c for c in ALL_CHECKS if not c.requires_runtime]
    first = run_checks(project, ast_checks)
    assert codes(first.findings) == ["DL001"]

    bp = tmp_path / "baseline"
    write_baseline(bp, first.findings)
    baseline = load_baseline(bp)
    assert len(baseline) == 1

    second = run_checks(project, ast_checks, baseline=baseline)
    assert second.findings == [] and codes(second.baselined) == ["DL001"]
    assert second.clean and second.baseline_size == 1

    # a stale entry (finding no longer fires) FAILS the run
    third = run_checks(
        Project([SourceFile(SERVING, "x = 1\n")]), ast_checks,
        baseline=baseline,
    )
    assert codes(third.findings) == ["DL000"]
    assert "stale baseline entry" in third.findings[0].message


def test_stale_detection_scoped_to_run_checks():
    """Regression: a partial run (--select / --ast-only) must not flag
    baseline entries belonging to checks that were deliberately skipped."""
    project = Project([SourceFile(SERVING, "x = 1\n")])
    ast_checks = [c for c in ALL_CHECKS if not c.requires_runtime]
    baseline = {"DL010 dnet_tpu/analysis/metrics_checks.py:0 some runtime finding": "why"}
    report = run_checks(project, ast_checks, baseline=baseline)
    assert report.findings == []  # DL010 did not run: entry is not stale
    # but an entry for a check that DID run and no longer fires IS stale
    baseline = {"DL001 dnet_tpu/api/gone.py:3 old finding": "why"}
    report = run_checks(project, ast_checks, baseline=baseline)
    assert [f.code for f in report.findings] == ["DL000"]


def test_write_baseline_excludes_meta_findings(tmp_path):
    """Regression: a stale-entry meta-finding ('<baseline>' pseudo-path)
    must never be written into a new baseline — it could never match a
    scanned file again and would poison every subsequent run."""
    project = Project([SourceFile(SERVING, "x = 1\n")])
    ast_checks = [c for c in ALL_CHECKS if not c.requires_runtime]
    report = run_checks(
        project, ast_checks,
        baseline={"DL001 dnet_tpu/api/gone.py:3 old finding": "why"},
    )
    assert [f.path for f in report.findings] == ["<baseline>"]
    bp = tmp_path / "baseline"
    write_baseline(bp, report.findings)
    assert load_baseline(bp) == {}


def test_env_flag_semantics():
    """Regression: set-but-empty keeps the default (DNET_FLASH_DECODE=
    must not silently disable the default-enabled flash kernel)."""
    import os

    from dnet_tpu.config import env_flag

    for name in ("DNET_ENVFLAG_FIXTURE",):
        os.environ.pop(name, None)
        assert env_flag(name) is False
        assert env_flag(name, default=True) is True
        try:
            os.environ[name] = ""
            assert env_flag(name, default=True) is True
            assert env_flag(name) is False
            os.environ[name] = "0"
            assert env_flag(name, default=True) is False
            os.environ[name] = "yes"
            assert env_flag(name) is True
            os.environ[name] = "garbage"
            assert env_flag(name, default=True) is True
        finally:
            os.environ.pop(name, None)


def test_cli_refuses_empty_check_set():
    """Regression: --select of a runtime-only check + --ast-only must not
    become a green no-op."""
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), "--select", "DL010", "--ast-only"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "no checks left to run" in proc.stderr


# ---- deterministic ordering ----------------------------------------------


def test_finding_order_is_deterministic():
    texts = {
        "dnet_tpu/api/b_mod.py": (
            "import os, time\n"
            "async def h():\n"
            "    time.sleep(1)\n"
            "V = os.environ.get('DNET_X')\n"
        ),
        "dnet_tpu/api/a_mod.py": (
            "import os\n"
            "W = os.environ.get('DNET_Y')\n"
        ),
    }
    runs = [analyze_texts(dict(reversed(list(texts.items())))),
            analyze_texts(texts)]
    assert runs[0] == runs[1]
    keys = [(f.path, f.line, f.col, f.code) for f in runs[0]]
    assert keys == sorted(keys)
    assert [f.path for f in runs[0]] == [
        "dnet_tpu/api/a_mod.py", "dnet_tpu/api/b_mod.py",
        "dnet_tpu/api/b_mod.py",
    ]


# ---- check catalog hygiene -------------------------------------------------


def test_check_codes_unique_and_documented():
    seen = set()
    for c in ALL_CHECKS:
        assert c.code not in seen, f"duplicate check code {c.code}"
        seen.add(c.code)
        assert c.description, f"{c.code} has no description"
    # the full 32-check catalog: DL001-DL009 + DL029 (AST), DL010-DL020 +
    # DL026-DL028 + DL030-DL032 (runtime metric passes), DL021-DL025
    # (flow-sensitive tier)
    assert seen == {f"DL{i:03d}" for i in range(1, 33)}


# ---- tier-1 self-run wrapper ----------------------------------------------


def test_dnetlint_self_run_clean(tmp_path):
    """The whole suite over THIS repo: exit 0, empty-or-justified
    baseline, JSON report carries the check catalog.  This is the tier-1
    gate that replaces reviewer memory with machine checks."""
    out = tmp_path / "analysis.json"
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), "--json", str(out)],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["clean"] is True
    assert report["files_scanned"] > 100
    # the FULL 32-check catalog ran: DL001-DL009 + DL029 AST, DL010-DL020
    # + DL026-DL028 + DL030-DL032 runtime metric passes, DL021-DL025
    # flow-sensitive tier — a check cannot silently fall out of the suite
    assert sorted(report["checks_run"]) == [
        f"DL{i:03d}" for i in range(1, 33)
    ]
    assert report["findings"] == []
    # the merged runtime-sanitizer section: the full DS catalog is always
    # present (dashboards rely on the shape) and this unsanitized run
    # contributed no findings
    runtime = report["runtime"]
    assert runtime["tool"] == "dsan"
    assert runtime["enabled_env"] == "DNET_SAN"
    assert [c["code"] for c in runtime["checks"]] == [
        "DS001", "DS002", "DS003", "DS004", "DS005", "DS006",
    ]
    assert all(c["description"] for c in runtime["checks"])
    assert isinstance(runtime["findings"], list)
    # the shipped baseline is empty (every entry would need a per-line
    # justification — the acceptance criterion)
    assert load_baseline(REPO / ".dnetlint-baseline") == {}


def test_dnetlint_list_checks_includes_runtime_catalog():
    """``--list-checks`` is the discoverability surface: it must name the
    static suite (DL001..DL018, DL009 among them) AND the dsan runtime
    catalog (DS001..DS006) so a developer sees both halves in one place."""
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), "--list-checks"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    listed = {
        line.split()[0] for line in proc.stdout.splitlines() if line.strip()
    }
    for code in ["DL009", "DS001", "DS002", "DS003", "DS004", "DS005", "DS006"]:
        assert code in listed, f"{code} missing from --list-checks"
    # the DS rows are tagged as dsan (runtime-process) checks
    ds_rows = [l for l in proc.stdout.splitlines() if l.startswith("DS")]
    assert ds_rows and all("[dsan" in l for l in ds_rows)


def test_dnetlint_detects_seeded_violation(tmp_path):
    """End-to-end negative control: the CLI must FAIL on a tree with a
    violation — proves the wrapper cannot rot into a green no-op."""
    root = tmp_path / "repo"
    (root / "dnet_tpu" / "api").mkdir(parents=True)
    (root / "dnet_tpu" / "api" / "bad.py").write_text(
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)\n"
    )
    sys.path.insert(0, str(REPO))
    from dnet_tpu.analysis import run_analysis

    report = run_analysis(root, include_runtime=False)
    assert not report.clean
    assert codes(report.findings) == ["DL001"]


# ---- CFG / dataflow mechanics (flow tier) ----------------------------------

import ast  # noqa: E402

from dnet_tpu.analysis.flow import (  # noqa: E402
    FLOW_CHECKS,
    build_cfg,
    definitely_assigned,
    jit_bindings,
    live_names,
    reaching_definitions,
)


def _cfg_of(src_text: str):
    fn = ast.parse(src_text).body[0]
    return build_cfg(fn)


def _node_at(cfg, line: int):
    hits = [n for n in cfg.nodes if n.line == line]
    assert hits, f"no CFG node at line {line}"
    return hits[0]


def test_cfg_branch_join_reaching_defs():
    """Both arms' defs of x reach the statement after the join."""
    cfg = _cfg_of(
        "def f(c):\n"
        "    if c:\n"       # 2
        "        x = 1\n"   # 3
        "    else:\n"
        "        x = 2\n"   # 5
        "    return x\n"    # 6
    )
    reach = reaching_definitions(cfg)
    use = _node_at(cfg, 6)
    def_lines = {
        cfg.nodes[i].line for (name, i) in reach[use.idx] if name == "x"
    }
    assert def_lines == {3, 5}
    # and x is definitely assigned at the join (both arms bind it)
    assert "x" in definitely_assigned(cfg)[use.idx]


def test_cfg_branch_without_else_not_definite():
    cfg = _cfg_of(
        "def f(c):\n"
        "    if c:\n"
        "        x = 1\n"
        "    return x\n"  # 4
    )
    assert "x" not in definitely_assigned(cfg)[_node_at(cfg, 4).idx]


def test_cfg_loop_back_edge():
    """A def at the loop bottom reaches a use at the loop top via the
    back edge — the edge per-node AST matching cannot see."""
    cfg = _cfg_of(
        "def f(xs):\n"
        "    acc = 0\n"          # 2
        "    for x in xs:\n"     # 3
        "        use(acc)\n"     # 4
        "        acc = step(x)\n"  # 5
        "    return acc\n"       # 6
    )
    assert cfg.back_edges, "loop produced no back edge"
    reach = reaching_definitions(cfg)
    use = _node_at(cfg, 4)
    def_lines = {
        cfg.nodes[i].line for (name, i) in reach[use.idx] if name == "acc"
    }
    assert def_lines == {2, 5}  # initial def AND the previous iteration's
    # liveness: acc is live at the loop header's exit (read at line 4)
    live = live_names(cfg)
    assert "acc" in live[_node_at(cfg, 3).idx]


def test_cfg_try_except_edges():
    """Any statement of a try body may raise: its IN-facts flow to the
    handler, so a def before the failing point reaches the except."""
    cfg = _cfg_of(
        "def f():\n"
        "    try:\n"
        "        x = open()\n"   # 3
        "        y = x.read()\n"  # 4
        "    except Exception:\n"  # 5
        "        return x\n"     # 6
        "    return y\n"         # 7
    )
    reach = reaching_definitions(cfg)
    handler_use = _node_at(cfg, 6)
    names = {name for (name, _) in reach[handler_use.idx]}
    assert "x" in names
    # but x is NOT definitely assigned in the handler (line 3 itself may
    # have raised before binding)
    assert "x" not in definitely_assigned(cfg)[handler_use.idx]
    # normal exit: y is definitely assigned at line 7
    assert "y" in definitely_assigned(cfg)[_node_at(cfg, 7).idx]


def test_cfg_break_terminates_path():
    cfg = _cfg_of(
        "def f(xs):\n"
        "    for x in xs:\n"   # 2
        "        if x:\n"      # 3
        "            y = 1\n"  # 4
        "            break\n"  # 5
        "    return y\n"       # 6
    )
    reach = reaching_definitions(cfg)
    use = _node_at(cfg, 6)
    assert any(name == "y" for (name, _) in reach[use.idx])
    assert "y" not in definitely_assigned(cfg)[use.idx]


def test_jit_bindings_resolution():
    """The jit model resolves wrappers and scoped locals."""
    from dnet_tpu.analysis import SourceFile as SF

    src = SF("dnet_tpu/ops/m.py", (
        "import jax\n"
        "from functools import partial\n"
        "def step(kv, x):\n"
        "    return kv\n"
        "class E:\n"
        "    def build(self):\n"
        "        self._step = instrument_jit(\n"
        "            jax.jit(step, donate_argnums=(0,)), 'batched_step')\n"
        "def fac_a():\n"
        "    jitted = jax.jit(step, donate_argnums=(0,))\n"
        "    return jitted\n"
        "def fac_b():\n"
        "    jitted = jax.jit(step, donate_argnums=(1,))\n"
        "    return jitted\n"
    ))
    b = jit_bindings(src)
    assert b["self._step"].donate == (0,)
    assert b["self._step"].label == "batched_step"
    # per-function scoping: the two functions' `jitted` locals don't collide
    assert b["fac_a:jitted"].donate == (0,)
    assert b["fac_b:jitted"].donate == (1,)


# ---- DL021 donation-after-use ---------------------------------------------

_OPS = "dnet_tpu/ops/fixture_mod.py"


def test_dl021_fires_on_read_after_donation():
    fs = findings_for(
        "import jax\n"
        "def step(kv, x):\n"
        "    return kv\n"
        "fn = jax.jit(step, donate_argnums=(0,))\n"
        "def drive(self, x):\n"
        "    out = fn(self.kv, x)\n"
        "    return self.kv.sum() + out\n",  # line 7: stale read
        rel=_OPS,
    )
    assert codes(fs) == ["DL021"] and fs[0].line == 7
    assert "donated" in fs[0].message


def test_dl021_fires_on_one_branch_only():
    """Flow-sensitivity: only the path that reads without a rebind fires."""
    fs = findings_for(
        "import jax\n"
        "def step(kv):\n"
        "    return kv\n"
        "fn = jax.jit(step, donate_argnums=(0,))\n"
        "def drive(self, c):\n"
        "    out = fn(self.kv)\n"
        "    if c:\n"
        "        self.kv = out\n"
        "    return self.kv\n",  # reachable with the stale name when not c
        rel=_OPS,
    )
    assert codes(fs) == ["DL021"] and fs[0].line == 9


def test_dl021_fires_on_loop_without_rebind():
    fs = findings_for(
        "import jax\n"
        "def step(kv):\n"
        "    return kv\n"
        "fn = jax.jit(step, donate_argnums=(0,))\n"
        "def drive(self, xs):\n"
        "    for x in xs:\n"
        "        out = fn(self.kv)\n"  # next iteration re-reads the corpse
        "    return out\n",
        rel=_OPS,
    )
    assert codes(fs) == ["DL021"] and fs[0].line == 7


def test_dl021_quiet_on_donate_and_rebind():
    """The sanctioned idiom: the calling statement rebinds the donated
    name — every subsequent read sees the fresh buffer."""
    fs = findings_for(
        "import jax\n"
        "def step(kv, x):\n"
        "    return kv, x\n"
        "fn = jax.jit(step, donate_argnums=(0,))\n"
        "def drive(self, x):\n"
        "    self.kv, y = fn(self.kv, x)\n"
        "    out = fn(self.kv, y)\n"
        "    self.kv = out[0]\n"
        "    return self.kv\n",
        rel=_OPS,
    )
    assert fs == []


def test_dl021_quiet_on_starred_args_rebind():
    """The *args idiom from core/batch.py: the donated position resolves
    through the local tuple, and the same-statement rebind stays quiet."""
    fs = findings_for(
        "import jax\n"
        "def step(wp, kv, keys):\n"
        "    return kv, keys\n"
        "fn = jax.jit(step, donate_argnums=(1, 2))\n"
        "def drive(self, wp):\n"
        "    args = (wp, self.kv_store.kv, self.keys)\n"
        "    pool, self.keys = fn(*args)\n"
        "    self.kv_store.kv = pool\n"
        "    return self.kv_store.kv\n",
        rel=_OPS,
    )
    assert fs == []


def test_dl021_real_batch_engine_rebind_idiom_is_quiet():
    """The live donate-and-rebind sites in core/batch.py (the dense
    step's donated cache rebound via `... self.kv, ... = out`) must stay
    quiet — they are the sanctioned pattern the check's message points
    at."""
    text = (REPO / "dnet_tpu" / "core" / "batch.py").read_text()
    fs = analyze_texts({"dnet_tpu/core/batch.py": text}, checks=FLOW_CHECKS)
    assert [f for f in fs if f.code == "DL021"] == []


# ---- DL022 retrace hazards ------------------------------------------------


def test_dl022_fires_on_shape_scalar_and_literal():
    fs = findings_for(
        "import jax\n"
        "def step(x, n, w):\n"
        "    return x * n * w\n"
        "fn = jax.jit(step)\n"
        "def drive(x):\n"
        "    return fn(x, x.shape[0], 4)\n",
        rel=_OPS,
    )
    assert codes(fs) == ["DL022", "DL022"]
    assert ".shape-derived" in fs[0].message
    assert "Python literal" in fs[1].message


def test_dl022_quiet_on_static_position_and_wrapped_scalar():
    fs = findings_for(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def step(x, n, w):\n"
        "    return x * n * w\n"
        "fn = jax.jit(step, static_argnums=(2,))\n"
        "def drive(x):\n"
        "    return fn(x, jnp.int32(x.shape[0]), 4)\n",  # static: fine
        rel=_OPS,
    )
    assert fs == []


def test_dl022_fires_on_kwarg_drift():
    fs = findings_for(
        "import jax\n"
        "fn = jax.jit(external_step)\n"
        "def a(x):\n"
        "    return fn(x)\n"
        "def b(x, m):\n"
        "    return fn(x, mode=m)\n",  # line 6: kwarg set differs
        rel=_OPS,
    )
    assert codes(fs) == ["DL022"] and fs[0].line == 6
    assert "drifts" in fs[0].message


def test_dl022_nested_scope_resolves_inner_args_tuple():
    """Regression: a call inside a nested def must resolve its *args
    splat against the NESTED scope's tuple (an outer tuple of the same
    name must not shadow it into unresolvability)."""
    fs = findings_for(
        "import jax\n"
        "fn = jax.jit(external_step)\n"
        "def outer(x):\n"
        "    args = (x, 1)\n"
        "    def inner(y):\n"
        "        args = (y, y.shape[0])\n"
        "        return fn(*args)\n"
        "    return inner\n",
        rel=_OPS,
    )
    assert codes(fs) == ["DL022"]
    assert ".shape-derived" in fs[0].message


def test_dl022_kwarg_drift_does_not_taint_absorbed_arity():
    """Regression: one kwarg-drifting site must not make a
    default-absorbed arity difference at ANOTHER site a finding."""
    fs = findings_for(
        "import jax\n"
        "def step(x, y, kinds=None):\n"
        "    return x\n"
        "fn = jax.jit(step)\n"
        "def a(x, y):\n"
        "    return fn(x, y)\n"
        "def b(x, y, k):\n"
        "    return fn(x, y, k)\n"       # absorbed by the default: quiet
        "def c(x, y, m):\n"
        "    return fn(x, y, mode=m)\n",  # line 10: kwarg drift fires
        rel=_OPS,
    )
    assert codes(fs) == ["DL022"] and fs[0].line == 10
    assert "keywords" in fs[0].message


def test_cli_rejects_diff_with_write_baseline():
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), "--diff", "HEAD", "--write-baseline"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "needs a full run" in proc.stderr


def test_dl022_quiet_when_optional_param_absorbs_arity():
    """core/engine.py's _hidden pattern: 5- and 6-arg sites of a callee
    with a defaulted trailing param are one contract, not drift."""
    fs = findings_for(
        "import jax\n"
        "def step(wp, x, kv, pos, t, kinds=None):\n"
        "    return x\n"
        "fn = jax.jit(step, donate_argnums=(2,))\n"
        "def a(self, wp, x, pos, t):\n"
        "    self.kv = fn(wp, x, self.kv, pos, t)\n"
        "def b(self, wp, x, pos, t, kinds):\n"
        "    self.kv = fn(wp, x, self.kv, pos, t, kinds)\n",
        rel=_OPS,
    )
    assert fs == []


# ---- DL023 host sync in hot loop ------------------------------------------

_SCHED = "dnet_tpu/sched/fixture_mod.py"


def test_dl023_fires_on_item_in_tick_loop():
    fs = findings_for(
        "def run(engine, plan):\n"
        "    for req in plan:\n"
        "        v = engine.score(req).item()\n",
        rel=_SCHED,
    )
    mine = [f for f in fs if f.code == "DL023"]
    assert len(mine) == 1 and mine[0].line == 3
    assert "loop" in mine[0].message


def test_dl023_fires_on_asarray_in_while_loop():
    fs = findings_for(
        "import numpy as np\n"
        "def drain(engine):\n"
        "    while engine.pending:\n"
        "        toks = np.asarray(engine.step())\n",
        rel=_SCHED,
    )
    assert [f.code for f in fs if f.code == "DL023"] == ["DL023"]


def test_dl023_quiet_outside_loop_and_gated_and_cold_files():
    # the sanctioned shape: ONE packed readback per dispatch, after the
    # loop that builds the batch — no sync per iteration
    fs = findings_for(
        "import numpy as np\n"
        "from dnet_tpu.obs import obs_enabled\n"
        "def run(engine, plan):\n"
        "    for req in plan:\n"
        "        engine.enqueue(req)\n"
        "        if obs_enabled():\n"
        "            engine.probe().item()\n"  # obs-gated fence: sanctioned
        "    toks = np.asarray(engine.flush())\n"  # packed readback: fine
        "    return toks\n",
        rel=_SCHED,
    )
    assert [f for f in fs if f.code == "DL023"] == []
    # the same loop sync in a NON-hot-loop module is DL005's business
    fs = findings_for(
        "def run(engine, plan):\n"
        "    for req in plan:\n"
        "        v = engine.score(req).item()\n",
        rel="dnet_tpu/membership/fixture_mod.py",
    )
    assert [f for f in fs if f.code == "DL023"] == []


# ---- DL024 sequential awaits in a loop ------------------------------------


def test_dl024_fires_on_independent_fanout():
    fs = findings_for(
        "async def fan(clients):\n"
        "    for c in clients:\n"
        "        await c.ping()\n"
    )
    assert codes(fs) == ["DL024"] and fs[0].line == 3
    assert "gather" in fs[0].message


def test_dl024_fires_with_per_iteration_temps():
    """Names assigned earlier in the SAME iteration are not loop-carried
    (the ring_manager load-body shape)."""
    fs = findings_for(
        "async def fan(client, devs):\n"
        "    for d in devs:\n"
        "        url = make_url(d)\n"
        "        r = await client.post(url)\n"
        "        if r.status != 200:\n"
        "            raise RuntimeError(url)\n"
    )
    assert codes(fs) == ["DL024"] and fs[0].line == 4


def test_dl024_quiet_on_loop_carried_dependency():
    fs = findings_for(
        "async def drain(fetch, pages):\n"
        "    cursor = None\n"
        "    for p in pages:\n"
        "        cursor = await fetch(p, cursor)\n"  # feeds next iteration
        "    return cursor\n"
    )
    assert fs == []


def test_dl024_quiet_on_exempt_shapes():
    fs = findings_for(
        "import asyncio, time\n"
        "async def f(resp, chunks, loop, fn, items, q):\n"
        "    for c in chunks:\n"
        "        await resp.write(c)\n"          # ordered sink
        "    for it in items:\n"
        "        await loop.run_in_executor(None, fn, it)\n"  # owned executor
        "    for it in items:\n"
        "        await asyncio.sleep(0.1)\n"     # pacing
        "    for it in items:\n"
        "        t0 = time.perf_counter()\n"     # measurement loop
        "        await q.probe(it)\n"
        "        record(time.perf_counter() - t0)\n"
        "    for it in items:\n"
        "        r = await q.get(it)\n"          # early exit: sequencing
        "        if r:\n"
        "            break\n"
    )
    assert fs == []


def test_dl024_quiet_off_serving_path_and_async_for():
    fs = findings_for(
        "async def fan(clients):\n"
        "    for c in clients:\n"
        "        await c.ping()\n",
        rel="dnet_tpu/cli/fixture_mod.py",
    )
    assert fs == []
    fs = findings_for(
        "async def pump(stream, sink):\n"
        "    async for item in stream:\n"
        "        await sink.handle(item)\n"
    )
    assert fs == []


# ---- DL025 wire dtype drift -----------------------------------------------

_SHARD = "dnet_tpu/shard/fixture_mod.py"


def test_dl025_fires_on_literal_dtype_serialize_and_parse():
    fs = findings_for(
        "import numpy as np\n"
        "from dnet_tpu.utils.serialization import tensor_to_bytes, bytes_to_tensor\n"
        "def send(x):\n"
        "    return tensor_to_bytes(np.asarray(x, dtype=np.float32))\n"
        "def send2(x):\n"
        "    return tensor_to_bytes(x, 'bfloat16')\n"
        "def recv(payload, shape):\n"
        "    return bytes_to_tensor(payload, 'float32', shape)\n",
        rel=_SHARD,
    )
    assert codes(fs) == ["DL025", "DL025", "DL025"]
    assert [f.line for f in fs] == [4, 6, 8]


def test_dl025_quiet_on_derived_dtype_and_token_frames():
    fs = findings_for(
        "import numpy as np\n"
        "from dnet_tpu.utils.serialization import tensor_to_bytes, bytes_to_tensor\n"
        "def send(self, x):\n"
        "    return tensor_to_bytes(\n"
        "        np.zeros((1, 4), np.float32), self.wire_dtype\n"  # cast wins
        "    )\n"
        "def send_tokens(ids):\n"
        "    return tensor_to_bytes(np.asarray(ids, dtype=np.int32))\n"  # int
        "def recv(payload, frame, shape):\n"
        "    return bytes_to_tensor(payload, frame.dtype, shape)\n",
        rel=_SHARD,
    )
    assert fs == []
    # outside the wire modules the check does not apply
    fs = findings_for(
        "from dnet_tpu.utils.serialization import tensor_to_bytes\n"
        "import numpy as np\n"
        "def embed(v):\n"
        "    return tensor_to_bytes(np.asarray(v, dtype=np.float32))\n",
        rel="dnet_tpu/loadgen/fixture_mod.py",
    )
    assert fs == []


# ---- seeded negative controls over the REAL hot files ----------------------


def _inject(rel: str, anchor: str, inserted: str, before: bool = True):
    """Insert a line (at the anchor's indentation) into the real file's
    text; returns (texts, injected_lineno)."""
    text = (REPO / rel).read_text()
    lines = text.splitlines(keepends=True)
    idx = next(i for i, l in enumerate(lines) if anchor in l)
    indent = lines[idx][: len(lines[idx]) - len(lines[idx].lstrip())]
    at = idx if before else idx + 1
    lines.insert(at, f"{indent}{inserted}\n")
    return {rel: "".join(lines)}, at + 1


def _flow_findings(texts):
    return analyze_texts(texts, checks=FLOW_CHECKS)


def test_seeded_dl021_donated_cache_read_after_the_batched_step():
    """Injecting a read of the donated cache between the batched step's
    call and its sanctioned rebind produces exactly one DL021 at that
    line; the clean file produces none."""
    rel = "dnet_tpu/core/batch.py"
    assert _flow_findings({rel: (REPO / rel).read_text()}) == []
    texts, line = _inject(
        rel, "flight.src, self.kv, self.counts, self.keys = out",
        "probe = jax.tree.map(jnp.shape, self.kv)",
    )
    fs = _flow_findings(texts)
    assert codes(fs) == ["DL021"], fs
    assert fs[0].line == line and "self.kv" in fs[0].message


def test_seeded_dl022_python_scalar_jit_argument():
    """Injecting a .shape-derived host scalar into a kv_gather dispatch
    produces exactly one DL022 at that line."""
    rel = "dnet_tpu/kv/store.py"
    assert _flow_findings({rel: (REPO / rel).read_text()}) == []
    texts, line = _inject(
        rel, "return self._gather(self.kv, ids)",
        "self._gather(self.kv, ids.shape[0])",
    )
    fs = _flow_findings(texts)
    assert codes(fs) == ["DL022"], fs
    assert fs[0].line == line and "non-static" in fs[0].message


def test_seeded_dl023_item_in_sched_tick_loop():
    """Injecting an .item() into the tick executor's prefill loop
    produces exactly one DL023 at that line."""
    rel = "dnet_tpu/sched/step.py"
    assert _flow_findings({rel: (REPO / rel).read_text()}) == []
    texts, line = _inject(
        rel, "if chunk.nonce in res.preempted:",
        "depth = plan.budgets.get(chunk.nonce).item()",
    )
    fs = _flow_findings(texts)
    assert codes(fs) == ["DL023"], fs
    assert fs[0].line == line and "item()" in fs[0].message


# ---- --select validation and --diff incremental mode -----------------------


def test_cli_rejects_unknown_select_codes():
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), "--select", "DL021,DL999"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "unknown check code(s) DL999" in proc.stderr
    assert "DL001" in proc.stderr  # the known-code list is printed


def _git(root, *argv):
    return subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *argv],
        capture_output=True, text=True, cwd=root, timeout=60, check=True,
    )


def test_diff_mode_lints_only_changed_files_and_agrees(tmp_path):
    """--diff semantics, library-level: a one-file change lints only that
    file, and the findings for it match the full run's."""
    from dnet_tpu.analysis import run_analysis
    from dnet_tpu.analysis.core import changed_files

    root = tmp_path / "repo"
    api = root / "dnet_tpu" / "api"
    api.mkdir(parents=True)
    clean = "async def ok():\n    return 1\n"
    (api / "good.py").write_text(
        "import time\n"
        "async def h():\n"
        "    time.sleep(1)\n"  # pre-existing violation in an UNCHANGED file
    )
    (api / "touched.py").write_text(clean)
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    (api / "touched.py").write_text(
        clean + "async def fan(cs):\n    for c in cs:\n        await c.ping()\n"
    )
    changed = changed_files(root, "HEAD")
    assert changed == {"dnet_tpu/api/touched.py"}
    diff_report = run_analysis(
        root, include_runtime=False, only_files=changed
    )
    # only the changed file's findings — good.py's DL001 is out of scope
    assert {f.path for f in diff_report.findings} == {"dnet_tpu/api/touched.py"}
    assert codes(diff_report.findings) == ["DL024"]
    full_report = run_analysis(root, include_runtime=False)
    assert [
        f for f in full_report.findings if f.path == "dnet_tpu/api/touched.py"
    ] == diff_report.findings


def test_cli_diff_head_is_fast_and_clean():
    """The pre-commit target: `dnetlint --diff HEAD` on this repo exits
    0 quickly (budget well under the full runtime-pass run)."""
    import time as _time

    t0 = _time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), "--diff", "HEAD"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    elapsed = _time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # target is <5s on a one-file change; allow slack for loaded CI hosts
    assert elapsed < 30, f"--diff HEAD took {elapsed:.1f}s"


def test_makefile_has_dnetlint_diff_target():
    text = (REPO / "Makefile").read_text()
    assert "dnetlint-diff:" in text
    assert "--diff $(REV)" in text


# ---- import direction: the layers below the api never import it -------------


@pytest.mark.parametrize("package", ["core", "kv", "ops", "models"])
def test_lower_layers_do_not_import_the_api(package):
    """api/ depends on core/, kv/, ops/ and models/, never the other way
    round (what both need lives in a leaf: core/types.py holds the typed
    errors core/ raises and api/http.py maps).  sched/ is not in the list:
    its adapter IS an api strategy."""
    hits = []
    for path in sorted((REPO / "dnet_tpu" / package).rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(node, ast.ImportFrom) and node.module:
                names = [node.module]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            hits += [
                f"{path.relative_to(REPO)}:{node.lineno} imports {n}"
                for n in names if n == "dnet_tpu.api" or n.startswith("dnet_tpu.api.")
            ]
    assert not hits, hits
