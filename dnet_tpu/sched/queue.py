"""Per-request scheduler state machine and priority queue.

Each request admitted by the API driver becomes one :class:`SchedRequest`
walking WAITING -> PREFILLING -> DECODING -> FINISHED.  Ordering is
deadline-first, then arrival (FIFO): the deadline is the one the PR 5
admission controller stamped on the request (``Deadline.t_deadline`` epoch
seconds, ridden through ``ApiAdapterBase.set_deadline``), so the scheduler
and the shedding layer agree on who is most urgent.  Preemption returns a
DECODING request to WAITING with its ``arrival`` unchanged — priority is a
stable total order, resources only ever flow up it, so preemption cannot
cycle.

The queue itself is loop-owned (declared in
``analysis/runtime/domains.py``, enforced under ``DNET_SAN=1``): policy
and bookkeeping run on the event loop; the compute thread only ever sees
plain snapshots inside a :class:`~dnet_tpu.sched.policy.TickPlan`.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from dnet_tpu.analysis.runtime import ownership as dsan
from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs import metric
from dnet_tpu.sched.kinds import (
    QUEUE_STATES,
    STATE_DECODING,
    STATE_FINISHED,
    STATE_PREFILLING,
    STATE_WAITING,
)

_QUEUE_DEPTH = metric("dnet_sched_queue_depth")


@dataclass
class SchedRequest:
    """One request's scheduler-side state.

    ``ids`` is the replay source: the prompt plus every generated token
    the driver has sent back (the driver echoes each accepted token as the
    next step's input, so appending at ``send_tokens`` time keeps ``ids``
    exactly one step ahead of the engine's committed KV).  A preempted
    request re-prefills ``ids`` wholesale; the prefix blocks aliased at
    eviction time make that mostly a block-table walk, not compute.
    """

    nonce: str
    ids: List[int]
    decoding: DecodingParams
    arrival: int
    prompt_len: int
    deadline_ts: Optional[float] = None
    state: str = STATE_WAITING
    #: inner-engine staging position: tokens of ``ids`` committed by
    #: chunked prefill so far (absolute, prefix-cache skips included)
    prefilled: int = 0
    #: the driver's outstanding step awaiting a token, or None
    pending_step: Optional[int] = None
    #: remaining token allowance the driver advertised with the pending
    #: step, that step's token included (the plan hands it to the engine:
    #: 2 or more, and the lane may be chained a step ahead, sched/step.py)
    pending_budget: Optional[int] = None
    preemptions: int = 0
    #: consecutive starved requeues (bounded before the typed error)
    starved: int = 0
    #: where the request waited (perf_counter stamps, loop-side): step-0
    #: enqueue, the start of the tick that ran its first prefill chunk, and
    #: its first token resolved; plus the prefill chunks (one per tick) it
    #: took.  sched/engine.py turns them into dnet_sched_queue_wait_ms /
    #: dnet_sched_prefill_wall_ms / dnet_sched_prefill_ticks and the
    #: recorder's sched_queue / prefill spans.
    t_enqueued: float = field(default_factory=time.perf_counter)
    t_first_chunk: Optional[float] = None
    t_first_token: Optional[float] = None
    prefill_chunks: int = 0
    #: when the token the driver now holds was resolved (perf_counter,
    #: loop-side), until its next send_tokens closes the interval into
    #: dnet_sched_answer_wait_ms
    t_token: Optional[float] = None
    extra: dict = field(default_factory=dict)

    def priority(self) -> Tuple[float, int]:
        """Sort key, smaller = more urgent: (deadline, arrival)."""
        return (
            self.deadline_ts if self.deadline_ts is not None else math.inf,
            self.arrival,
        )


class SchedQueue:
    """nonce -> SchedRequest map with priority views and depth gauges."""

    def __init__(self) -> None:
        self._arrival = 0
        self._reqs: Dict[str, SchedRequest] = dsan.guard_dict(
            {}, dsan.loop_domain(), "SchedQueue._reqs"
        )

    def __len__(self) -> int:
        return len(self._reqs)

    def __contains__(self, nonce: str) -> bool:
        return nonce in self._reqs

    def get(self, nonce: str) -> Optional[SchedRequest]:
        return self._reqs.get(nonce)

    def add(
        self,
        nonce: str,
        prompt_ids: List[int],
        decoding: DecodingParams,
        deadline_ts: Optional[float] = None,
    ) -> SchedRequest:
        self._arrival += 1
        req = SchedRequest(
            nonce=nonce,
            ids=list(prompt_ids),
            decoding=decoding,
            arrival=self._arrival,
            prompt_len=len(prompt_ids),
            deadline_ts=deadline_ts,
        )
        self._reqs[nonce] = req
        self.sync_gauges()
        return req

    def remove(self, nonce: str) -> Optional[SchedRequest]:
        req = self._reqs.pop(nonce, None)
        if req is not None:
            req.state = STATE_FINISHED
            self.sync_gauges()
        return req

    def by_state(self, state: str) -> List[SchedRequest]:
        return [r for r in self._reqs.values() if r.state == state]

    def waiting(self) -> List[SchedRequest]:
        """WAITING requests, most urgent first."""
        return sorted(self.by_state(STATE_WAITING), key=SchedRequest.priority)

    def prefilling(self) -> List[SchedRequest]:
        """PREFILLING requests, most urgent first."""
        return sorted(
            self.by_state(STATE_PREFILLING), key=SchedRequest.priority
        )

    def decoding(self) -> List[SchedRequest]:
        return self.by_state(STATE_DECODING)

    def victims(self) -> List[str]:
        """DECODING nonces, LEAST urgent first — the eviction order when
        the block pool starves."""
        return [
            r.nonce
            for r in sorted(
                self.by_state(STATE_DECODING),
                key=SchedRequest.priority,
                reverse=True,
            )
        ]

    def requeue(self, nonce: str, reason_preempt: bool) -> None:
        """Return a running request to WAITING (preemption / starvation);
        its staged prefill is gone but ``arrival`` — and so priority — is
        unchanged."""
        req = self._reqs.get(nonce)
        if req is None:
            return
        req.state = STATE_WAITING
        req.prefilled = 0
        if reason_preempt:
            req.preemptions += 1
        else:
            req.starved += 1
        self.sync_gauges()

    def active(self) -> int:
        """Requests currently holding engine-side residency."""
        return len(self.by_state(STATE_PREFILLING)) + len(
            self.by_state(STATE_DECODING)
        )

    def sync_gauges(self) -> None:
        counts = {s: 0 for s in QUEUE_STATES}
        for r in self._reqs.values():
            if r.state in counts:
                counts[r.state] += 1
        for state, n in counts.items():
            _QUEUE_DEPTH.labels(state=state).set(float(n))
