"""dsan declaration data — the ownership registry and runtime-check catalog.

A LEAF module (stdlib only, imports nothing from dnet_tpu) so that

- instrumented modules (shard/runtime, api/strategies, kv/paged, ...) can
  apply guards by declared name without importing the checker machinery,
- ``dnet_tpu/obs`` can pre-touch the ``dnet_san_*`` label sets at registry
  init without a cycle, and
- the DL009 static check can cross-reference the declarations against the
  code purely from the AST (the declarations below are also parsed as a
  literal by the check's fixture mode).

Everything here is DATA.  The enforcement lives in the sibling modules
(ownership.py / lockorder.py / loop_monitor.py / tasks.py) and in
``dnet_tpu/analysis/checks_dsan.py`` (DL009) / ``metrics_checks.py``
(DL018).
"""

from __future__ import annotations

#: The runtime (dsan) check catalog: (code, name, description).  Shown by
#: ``dnetlint --list-checks``, embedded in the ANALYSIS report's
#: ``runtime`` section, and the label set of dnet_san_findings_total.
RUNTIME_CHECKS = (
    (
        "DS001", "loop-stall",
        "event loop blocked past DNET_SAN_STALL_MS; offending stack "
        "captured via sys._current_frames and attributed to file:line",
    ),
    (
        "DS002", "wrong-thread-access",
        "a structure declared loop-only / thread(<name>) was touched from "
        "a thread outside its ownership domain",
    ),
    (
        "DS003", "lock-not-held",
        "a structure declared guarded-by(<lock>) was touched without the "
        "declared lock held by the current thread",
    ),
    (
        "DS004", "lock-order-cycle",
        "instrumented locks were acquired in cyclic order across threads "
        "(potential deadlock)",
    ),
    (
        "DS005", "task-leak",
        "an asyncio task created during the sanitized window was still "
        "pending (never awaited or cancelled) at the teardown audit",
    ),
    (
        "DS006", "unretrieved-task-exception",
        "an asyncio task finished with an exception nobody retrieved "
        "(the failure would only surface as a GC-time log line, if ever)",
    ),
)

RUNTIME_CHECK_CODES = tuple(c for c, _, _ in RUNTIME_CHECKS)

#: Ownership declarations for the known hot thread/loop boundaries:
#: (module rel-path, class, attribute, kind, arg).
#:
#: kind ``loop``   — only the owning event loop's thread may touch it
#:                   (arg unused; the owning loop is bound at guard time)
#: kind ``thread`` — only threads named ``arg`` (exact, or ``arg_N`` for
#:                   executor pools) may touch the listed operations
#: kind ``lock``   — the instrumented lock attribute named ``arg`` on the
#:                   same instance must be held by the current thread
#:
#: DL009 verifies each declared module/class/attribute (and, for ``lock``
#: kind, the lock attribute) still exists in the code — a refactor cannot
#: silently strand the registry.
OWNERSHIP_DOMAINS = (
    ("dnet_tpu/shard/runtime.py", "ShardRuntime", "recv_q", "thread", "shard-compute"),
    ("dnet_tpu/shard/runtime.py", "ShardRuntime", "out_q", "loop", ""),
    ("dnet_tpu/shard/runtime.py", "ShardRuntime", "epoch", "lock", "_model_lock"),
    ("dnet_tpu/shard/runtime.py", "ShardRuntime", "_pending_errs", "loop", ""),
    ("dnet_tpu/api/strategies.py", "LocalAdapter", "_buffered", "lock", "_buf_lock"),
    ("dnet_tpu/api/strategies.py", "LocalAdapter", "_ramp", "lock", "_buf_lock"),
    ("dnet_tpu/kv/paged.py", "BlockPool", "_free", "lock", "_lock"),
    ("dnet_tpu/kv/paged.py", "BlockPool", "_ref", "lock", "_lock"),
    ("dnet_tpu/core/prefix_cache.py", "PrefixIndex", "_entries", "lock", "_lock"),
    ("dnet_tpu/obs/metrics.py", "MetricsRegistry", "_metrics", "lock", "_lock"),
    ("dnet_tpu/transport/stream_manager.py", "StreamManager", "_streams", "loop", ""),
    # iteration-level scheduler (dnet_tpu/sched/): the queue, the
    # pre-arrival deadline stash and the set of drivers a tick still waits
    # to hear from are loop-owned — the compute thread only ever sees
    # plain snapshots inside a TickPlan
    ("dnet_tpu/sched/queue.py", "SchedQueue", "_reqs", "loop", ""),
    ("dnet_tpu/sched/engine.py", "SchedulerAdapter", "_deadlines", "loop", ""),
    ("dnet_tpu/sched/engine.py", "SchedulerAdapter", "_answering", "loop", ""),
    # overlapped wire pipeline (transport/wire_pipeline.py): the encode
    # ring's in-flight count is touched from the compute thread (acquire)
    # AND the tx executor (release) — guarded-by lock; the tx stage's
    # pending map is egress-worker-only (loop)
    ("dnet_tpu/transport/wire_pipeline.py", "EncodeRing", "_inflight", "lock", "_lock"),
    ("dnet_tpu/transport/wire_pipeline.py", "WireTxStage", "_pending", "loop", ""),
)

#: Modules sanctioned to cross the thread->loop boundary via
#: ``call_soon_threadsafe`` / ``run_coroutine_threadsafe``.  Anywhere else
#: such a bridge is a DL009 finding: ad-hoc bridges are exactly the seams
#: dsan exists to fence, so new ones must be declared here (and annotated)
#: or rewritten through an existing bridge.
BRIDGE_MODULES = (
    "dnet_tpu/shard/runtime.py",
    "dnet_tpu/api/strategies.py",
    "dnet_tpu/analysis/runtime/loop_monitor.py",
    # wire-pipeline tick dispatch: the scheduler's compute-thread tick
    # hands each decode result back to the loop as it is produced
    # (call_soon_threadsafe) instead of barriering on the full tick
    "dnet_tpu/sched/engine.py",
)

#: Label set of dnet_san_zombie_threads_total: worker threads that can
#: fail to join at stop() and get leaked as daemons (DL018 cross-checks
#: these against the exposed series both ways).
ZOMBIE_THREAD_KINDS = ("shard-compute", "tui")
