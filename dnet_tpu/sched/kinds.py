"""Scheduler declaration data — label enums for the dnet_sched_* families.

A LEAF module (stdlib only, imports nothing from dnet_tpu) so that
``dnet_tpu/obs`` can pre-touch the label sets at registry init without a
cycle, and the metrics lint (pass 10, DL019) can cross-check the exposed
series against these declarations from either direction — the same
pattern as ``admission/reasons.py`` and ``membership/epoch.py``.
"""

from __future__ import annotations

#: Per-request scheduler states (queue.py state machine).  ``finished`` is
#: terminal and never holds queue residency, so the queue-depth gauge only
#: carries the three live states below.
STATE_WAITING = "waiting"
STATE_PREFILLING = "prefilling"
STATE_DECODING = "decoding"
STATE_FINISHED = "finished"

#: Label set of dnet_sched_queue_depth{state=}: requests resident in the
#: scheduler queue by state.
QUEUE_STATES = (STATE_WAITING, STATE_PREFILLING, STATE_DECODING)

#: Label set of dnet_sched_batch_tokens{kind=}: per-tick batch composition
#: — how many prompt tokens rode chunked-prefill segments and how many
#: sequences took a decode step in the same tick.
BATCH_KINDS = ("prefill", "decode")

#: Label set of dnet_sched_preemptions_total{reason=}.
#: ``block_starvation`` — the paged-KV pool could not cover a decode
#: extension or a prefill chunk, so the lowest-priority running sequence
#: was evicted back to WAITING (paged prefix aliased into the prefix
#: cache where possible, so resume re-prefills only the uncovered tail).
#: ``starved_requeue`` — a PREFILLING request gave its staged work back
#: and returned to WAITING because the pool could not cover its next
#: chunk and no lower-priority victim existed.
PREEMPT_REASONS = ("block_starvation", "starved_requeue")

#: Label set of dnet_sched_mixed_ticks_total{overlapped=}: ticks that
#: carried a decode step AND a prefill chunk.  ``yes`` — every chunk was
#: enqueued behind the step before the step's blocking read, so the device
#: had work through the read (sched/step.py's rule).  ``no`` — the launch
#: half had already waited the device out (a per-lane verify block reads
#: its acceptance counts), so the chunks started on a drained device.
MIXED_TICK_OVERLAP = ("yes", "no")
