"""Paged KV block-pool allocator: free list, page tables, refcounted COW.

The dense engines pin `slots x max_seq` KV rows regardless of actual
sequence lengths (core/kvcache.py `init_cache`, core/batch.py's slot
model), and prefix reuse deep-copies whole snapshots
(core/prefix_cache.py `_copy_tree`).  Ragged Paged Attention (PAPERS.md)
shows the TPU-native alternative: block-granular KV with per-sequence
page tables — prefix sharing becomes refcounted block aliasing, and
admission becomes a function of FREE BLOCKS, not worst-case length.

This module is the host-side half: a `BlockPool` (allocation, refcounts,
exact accounting, typed backpressure) and per-sequence `PageTable`s
mapping logical block index -> physical pool block.  The device half
(`kv/store.py`) holds the pool-shaped cache arrays and the jitted
programs that attend, append to and commit into them.  Everything here is plain Python under one lock: allocator decisions
are control flow, never traced.

Invariants (enforced by `check_conservation`, linted from tier-1 via
scripts/check_metrics_names.py):

- ``blocks_used + blocks_free == pool_blocks`` at every step; a block
  shared by N holders counts ONCE in used (that is the whole saving).
- every allocated block's refcount equals the number of holders (page
  tables + prefix-cache entries) that will eventually `free` it.
- pool exhaustion raises `KVPoolExhausted` — a clean backpressure signal
  the serving layer maps to queueing/429, never a shape error or OOM.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from dnet_tpu.analysis.runtime import ownership as dsan
from dnet_tpu.obs import metric
from dnet_tpu.obs.phases import KV_KIND_FULL, KV_KIND_WINDOW

_USED = metric("dnet_kv_blocks_used")
_FREE = metric("dnet_kv_blocks_free")
_POOL = metric("dnet_kv_pool_blocks")
_COW = metric("dnet_kv_cow_copies_total")
_SHARED = metric("dnet_kv_prefix_shared_blocks_total")
_REJECTED = metric("dnet_kv_admission_rejected_total")
_RELEASED = metric("dnet_kv_window_blocks_released_total")


class KVPoolExhausted(RuntimeError):
    """Typed backpressure: the paged pool cannot cover an admission or an
    extension.  Callers queue / shed load; they must never see this as a
    shape/OOM crash mid-program."""

    def __init__(self, need: int, free: int, total: int) -> None:
        super().__init__(
            f"paged KV pool exhausted: need {need} block(s), "
            f"{free} free of {total}"
        )
        self.need = need
        self.free = free
        self.total = total


def ceil_div(n: int, d: int) -> int:
    return -(-n // d)


@dataclass(frozen=True)
class PagedKVConfig:
    """Pool geometry, resolved from DNET_KV_* settings by the engines."""

    block_tokens: int
    pool_blocks: int

    def __post_init__(self) -> None:
        if self.block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1, got {self.block_tokens}")
        if self.pool_blocks < 1:
            raise ValueError(f"pool_blocks must be >= 1, got {self.pool_blocks}")

    @classmethod
    def from_settings(cls, max_seq: int, slots: int = 1) -> "PagedKVConfig":
        """Resolve block/pool sizing from KVSettings; pool_blocks=0 auto-
        sizes to the dense equivalent (slots x max_seq worth of blocks), so
        the pool never ADMITS less than dense slots would — the wins come
        from sharing and variable lengths."""
        from dnet_tpu.config import get_settings

        kv = get_settings().kv
        bt = int(kv.block_tokens)
        if bt < 1 or max_seq % bt:
            raise ValueError(
                f"DNET_KV_BLOCK_TOKENS={bt} must be >= 1 and divide "
                f"max_seq={max_seq}"
            )
        pool = int(kv.pool_blocks) or slots * ceil_div(max_seq, bt)
        return cls(block_tokens=bt, pool_blocks=pool)

    def blocks_for(self, n_tokens: int) -> int:
        return ceil_div(n_tokens, self.block_tokens)


@dataclass
class PageTable:
    """One sequence's logical->physical block map.

    `blocks[i]` backs tokens [(base+i)*bt, (base+i+1)*bt); `shared_upto`
    marks how many LEADING blocks are refcount-aliased from a prefix entry
    (full blocks only — immutable for this sequence, so decode never
    writes them; the partial tail of a shared prefix is COW-copied at
    adoption).  `base` is 0 for a table that keeps everything; a WINDOW
    layer's table has given back its first `base` logical blocks
    (`BlockPool.release_behind`), and prefix sharing is off for it."""

    blocks: List[int] = field(default_factory=list)
    shared_upto: int = 0
    base: int = 0

    def __len__(self) -> int:
        return len(self.blocks)


def window_blocks(window: int, block_tokens: int, step_tokens: int) -> int:
    """The most blocks one sequence's WINDOW-kind table ever holds: the
    window, the tokens one dispatch may add before the blocks behind it are
    given back (a prefill chunk; a decode step adds two at most), and one
    block for the edges the window and the step cut."""
    return ceil_div(window + step_tokens, block_tokens) + 1


def window_first_block(n_tokens: int, window: int, block_tokens: int) -> int:
    """Logical index of the first block a window layer still needs when the
    NEXT token sits at position n_tokens: it attends keys at positions
    > n_tokens - window."""
    return max(n_tokens - window + 1, 0) // block_tokens


class BlockPool:
    """Fixed-capacity block allocator with refcounts and exact accounting,
    for the layers of ONE kind (obs/phases.py KV_KINDS): a model whose
    layers all keep everything has one pool of the `full` kind; window
    layers get a pool of their own, whose tables give blocks back."""

    def __init__(self, cfg: PagedKVConfig, kind: str = KV_KIND_FULL) -> None:
        self.cfg = cfg
        self.kind = kind
        self._used_g = _USED.labels(kind=kind)
        self._free_g = _FREE.labels(kind=kind)
        self.block_tokens = cfg.block_tokens
        self.total = cfg.pool_blocks
        # every _free/_ref touch happens under _lock; the guarded-by
        # contract is declared in analysis/runtime/domains.py and enforced
        # under DNET_SAN=1 (plain containers otherwise)
        self._lock = dsan.san_lock("BlockPool._lock")
        _dom = dsan.maybe_lock_domain(self._lock)
        self._free: List[int] = dsan.guard_list(
            list(range(self.total)), _dom, "BlockPool._free"
        )
        self._ref: Dict[int, int] = dsan.guard_dict({}, _dom, "BlockPool._ref")
        # high-water mark of used blocks (tests/bench read it; the gauge
        # only shows the current value)
        self.peak_used = 0
        _POOL.labels(kind=kind).set(self.total)
        self._publish()

    # ---- accounting ---------------------------------------------------
    @property
    def used(self) -> int:
        with self._lock:
            return len(self._ref)

    @property
    def free(self) -> int:
        with self._lock:
            return len(self._free)

    def _publish(self) -> None:
        # caller holds no lock: values may be momentarily torn between the
        # two gauges, but each gauge is itself consistent
        with self._lock:
            used, free = len(self._ref), len(self._free)
            if used > self.peak_used:
                self.peak_used = used
        self._used_g.set(used)
        self._free_g.set(free)

    def can_cover(self, n_blocks: int) -> bool:
        with self._lock:
            return len(self._free) >= n_blocks

    def require(self, n_blocks: int) -> None:
        """Admission pre-check: raise KVPoolExhausted (and count the
        rejection) if the pool cannot cover n_blocks RIGHT NOW — the
        fail-before-compute gate prefill paths call before burning a
        forward pass."""
        with self._lock:
            free = len(self._free)
        if free < n_blocks:
            _REJECTED.inc()
            raise KVPoolExhausted(n_blocks, free, self.total)

    # ---- allocation ---------------------------------------------------
    def alloc(self, n_blocks: int) -> List[int]:
        """Allocate n fresh blocks (ref=1 each) or raise KVPoolExhausted
        WITHOUT a partial allocation."""
        if n_blocks == 0:
            return []
        with self._lock:
            if len(self._free) < n_blocks:
                need, free = n_blocks, len(self._free)
                _REJECTED.inc()
                raise KVPoolExhausted(need, free, self.total)
            out = [self._free.pop() for _ in range(n_blocks)]
            for b in out:
                self._ref[b] = 1
        self._publish()
        return out

    def retain(self, blocks: Sequence[int]) -> List[int]:
        """Take one extra reference per block (no sharing metric — for
        transient holds, e.g. keeping a prefix entry's blocks alive while
        their contents are gathered/copied)."""
        if not blocks:
            return []
        with self._lock:
            for b in blocks:
                if b not in self._ref:
                    raise ValueError(f"retain of unallocated block {b}")
                self._ref[b] += 1
        return list(blocks)

    def share(self, blocks: Sequence[int]) -> List[int]:
        """Alias existing blocks (ref++ each); returns them for chaining.
        Counts toward dnet_kv_prefix_shared_blocks_total — every call site
        is a copy the dense path would have made."""
        out = self.retain(blocks)
        if out:
            _SHARED.inc(len(out))
        return out

    @staticmethod
    def count_cow(n: int = 1) -> None:
        """Record COW copies performed OUTSIDE `cow()` (e.g. a partial
        shared block whose merged contents are committed from a dense
        working view instead of copied pool->pool)."""
        if n > 0:
            _COW.inc(n)

    def free_blocks(self, blocks: Sequence[int]) -> int:
        """Drop one reference per block; blocks reaching ref 0 return to
        the free list.  Returns how many became free."""
        if not blocks:
            return 0
        released = 0
        with self._lock:
            for b in blocks:
                r = self._ref.get(b)
                if r is None:
                    raise ValueError(f"free of unallocated block {b}")
                if r == 1:
                    del self._ref[b]
                    self._free.append(b)
                    released += 1
                else:
                    self._ref[b] = r - 1
        self._publish()
        return released

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._ref.get(block, 0)

    def cow(self, block: int) -> int:
        """Copy-on-write: allocate a fresh block to replace a SHARED one
        this sequence is about to diverge into; the caller copies the
        device contents (kv/store.py) and drops its reference on the old
        block.  Returns the new physical block id."""
        new = self.alloc(1)[0]
        self.free_blocks([block])
        _COW.inc()
        return new

    # ---- table helpers ------------------------------------------------
    def ensure(self, table: PageTable, n_tokens: int) -> List[int]:
        """Grow `table` to cover n_tokens (appending fresh blocks); returns
        the newly appended block ids.  All-or-nothing on exhaustion."""
        need = self.cfg.blocks_for(n_tokens) - table.base - len(table.blocks)
        if need <= 0:
            return []
        fresh = self.alloc(need)
        table.blocks.extend(fresh)
        return fresh

    def release_behind(self, table: PageTable, first_block: int) -> int:
        """Give back the leading blocks of a WINDOW layer's table that lie
        wholly before logical block `first_block` (window_first_block): the
        sequence advanced and no later token attends them.  Returns how
        many went back to the free list."""
        n = min(max(first_block - table.base, 0), len(table.blocks))
        if n == 0:
            return 0
        gone = table.blocks[:n]
        del table.blocks[:n]
        table.base += n
        self.free_blocks(gone)
        _RELEASED.inc(n)
        return n

    def release_table(self, table: Optional[PageTable]) -> int:
        if table is None or not table.blocks:
            return 0
        n = self.free_blocks(table.blocks)
        table.blocks.clear()
        table.shared_upto = 0
        table.base = 0
        return n

    # ---- invariants ---------------------------------------------------
    def check_conservation(self, holders: Optional[Sequence[Sequence[int]]] = None) -> None:
        """Assert the pool's books balance: used + free == total, the free
        list is duplicate-free and disjoint from allocated blocks, and —
        when the caller passes every live holder's block list — refcounts
        equal the number of holders per block."""
        with self._lock:
            used = len(self._ref)
            free = list(self._free)
            refs = dict(self._ref)
        if used + len(free) != self.total:
            raise AssertionError(
                f"paged pool leak: used {used} + free {len(free)} != "
                f"total {self.total}"
            )
        if len(set(free)) != len(free):
            raise AssertionError("paged pool free list has duplicates")
        if set(free) & set(refs):
            raise AssertionError("paged pool free list overlaps allocated blocks")
        if any(r < 1 for r in refs.values()):
            raise AssertionError("paged pool holds a block with refcount < 1")
        if holders is not None:
            counts: Dict[int, int] = {}
            for blocks in holders:
                for b in blocks:
                    counts[b] = counts.get(b, 0) + 1
            if counts != refs:
                raise AssertionError(
                    f"paged pool refcounts {refs} != holder counts {counts}"
                )

