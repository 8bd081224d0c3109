"""Iteration-level continuous-batching scheduler.

One serving engine for mixed prefill + decode: each tick packs a token
budget of chunked-prefill segments and one decode step per running
sequence into a single batch plan, admits work as a function of free
paged-KV blocks, and preempts by block starvation with the paged prefix
kept intact.  See README "Continuous batching" and ROADMAP item 1.

This ``__init__`` resolves its exports LAZILY (PEP 562): the metrics
registry's core registration imports ``sched.kinds`` for the label
declarations, and an eager ``engine``/``queue`` import here would
re-enter the registry lock through their module-level ``metric()``
handles — the same hazard ``dnet_tpu/admission/__init__.py`` documents.
"""

from __future__ import annotations

_EXPORTS = {
    "BATCH_KINDS": "dnet_tpu.sched.kinds",
    "PREEMPT_REASONS": "dnet_tpu.sched.kinds",
    "QUEUE_STATES": "dnet_tpu.sched.kinds",
    "PrefillChunk": "dnet_tpu.sched.policy",
    "SchedulerPolicy": "dnet_tpu.sched.policy",
    "TickPlan": "dnet_tpu.sched.policy",
    "SchedQueue": "dnet_tpu.sched.queue",
    "SchedRequest": "dnet_tpu.sched.queue",
    "SchedulerAdapter": "dnet_tpu.sched.engine",
    "TickResult": "dnet_tpu.sched.step",
    "execute_tick": "dnet_tpu.sched.step",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    target = _EXPORTS.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(target), name)
