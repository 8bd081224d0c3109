"""Block-level prefix sharing over the paged pool.

The dense `PrefixCache` stores one FULL deep-copied KV snapshot per
prompt (`_copy_tree`), so a multi-turn conversation's snapshots duplicate
their shared history once per turn.  Here the same `PrefixIndex` matcher
resolves hits to refcounted BLOCK RUNS in the pool instead:

- **store dedup**: a snapshot whose prompt extends an existing entry
  aliases the parent's full blocks (ref++, `dnet_kv_prefix_shared_blocks_
  total`) and commits only its own tail blocks — turn N's snapshot costs
  O(new turn), not O(history).
- **adoption** (`lookup_blocks`): the batched engine's page tables alias
  an entry's full blocks directly — no copy at all; the partial tail
  block (a request diverging mid-block) is COW-copied by the adopter.
- **staged store** (`store`): a prompt still staging on the inner B=1
  engine (chunked prefill) snapshots out of its dense row, committing
  only the tail blocks a parent entry doesn't already hold.

Entry eviction releases the entry's references through PrefixIndex's
`on_evict` hook; the blocks themselves live until the last page table
drops them.
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence, Tuple, Union

from dnet_tpu.core.prefix_cache import PrefixIndex
from dnet_tpu.kv.paged import BlockPool, KVPoolExhausted
from dnet_tpu.kv.store import KindStore
from dnet_tpu.utils.logger import get_logger

log = get_logger()


def prefix_affinity_key(prefix: Union[str, Sequence[int]], n_units: int = 256) -> str:
    """Stable hash of a conversation's leading prefix units.

    The fleet front door (fleet/router.py) keys its affinity table on
    this: two requests that share a prompt prefix — turn N and turn N+1
    of one conversation — hash to the same key, so the router can stick
    them to the replica whose pool already holds the shared blocks.  The
    front door has no tokenizer, so it hashes the first `n_units`
    text characters (or token ids when the caller has them — the same
    leading-run identity `PrefixIndex.lookup` matches on).
    """
    if isinstance(prefix, str):
        raw = prefix[:n_units].encode("utf-8", errors="replace")
    else:
        raw = b"\x00".join(
            str(int(t)).encode("ascii") for t in list(prefix)[:n_units]
        )
    return hashlib.sha256(raw).hexdigest()[:16]


class PagedPrefixCache:
    """PrefixIndex entries valued (n_tokens, block run) in a shared pool."""

    def __init__(
        self,
        pool: BlockPool,
        store: KindStore,
        capacity: int,
        min_tokens: int = 16,
    ) -> None:
        self.pool = pool
        self._dev = store
        self._index = PrefixIndex(
            capacity, min_tokens, kind="prefix", on_evict=self._release
        )
        self.stats = {"hits": 0, "misses": 0, "stores": 0}

    # PrefixCache-compat knob (tests tune it for tiny prompts)
    @property
    def min_tokens(self) -> int:
        return self._index.min_tokens

    @min_tokens.setter
    def min_tokens(self, v: int) -> None:
        self._index.min_tokens = v

    def _release(self, value) -> None:
        _n, blocks = value
        self.pool.free_blocks(blocks)

    # ---- block surface (batched engine aliasing) ----------------------
    def lookup_blocks(
        self, prompt_ids: Sequence[int]
    ) -> Optional[Tuple[int, List[int], int]]:
        """Longest-prefix hit as (n_tokens, blocks, n_full).

        The first `n_full` blocks are FULL and aliased (counted shared);
        a trailing partial block (n % block_tokens != 0) is retained
        uncounted — the adopter must COW it before writing and drop the
        transient reference afterwards.  The caller owns exactly one
        reference on every returned block."""
        hit = self._index.lookup(prompt_ids)
        if hit is None:
            self.stats["misses"] += 1
            return None
        self.stats["hits"] += 1
        n, (n_entry, blocks) = hit
        assert n == n_entry
        n_full = n // self.pool.block_tokens
        out = self.pool.share(blocks[:n_full])
        out += self.pool.retain(blocks[n_full:])
        return n, out, n_full

    def store_blocks(
        self, prompt_ids: Sequence[int], n_tokens: int, blocks: Sequence[int]
    ) -> bool:
        """Snapshot a live page table by aliasing its blocks (the batched
        store path: zero copies).  Safe because rows < n_tokens of every
        aliased block are immutable — the owning slot only ever rewrites
        its partial tail block's rows >= n_tokens, and adopters COW that
        block before writing."""
        ids = list(prompt_ids)
        if len(ids) < self.min_tokens or n_tokens != len(ids):
            return False
        if self._index.get_exact(ids) is not None:
            return False
        nb = self.pool.cfg.blocks_for(n_tokens)
        entry = self.pool.share(list(blocks[:nb]))
        if not self._index.put(ids, (n_tokens, entry)):
            self.pool.free_blocks(entry)
            return False
        self.stats["stores"] += 1
        return True

    # ---- staged store (a prompt still on the inner B=1 engine) --------
    def store(self, prompt_ids: Sequence[int], kv_row: dict) -> None:
        """Snapshot a dense session row, committing only the tail blocks a
        parent entry doesn't already hold (block-level dedup)."""
        ids = list(prompt_ids)
        n = len(ids)
        if n < self.min_tokens:
            return
        if self._index.get_exact(ids) is not None:
            return
        bt = self.pool.block_tokens
        nb = self.pool.cfg.blocks_for(n)
        parent = self._index.match_quiet(ids, allow_equal=False)
        n_parent_full = (parent[0] // bt) if parent is not None else 0
        try:
            own = self.pool.alloc(nb - n_parent_full)
        except KVPoolExhausted as exc:
            # a full pool must not fail the REQUEST over a snapshot; the
            # admission path is where exhaustion is a hard signal
            log.warning("paged prefix store skipped: %s", exc)
            return
        aliased = (
            self.pool.share(parent[1][1][:n_parent_full])
            if parent is not None
            else []
        )
        self._dev.commit_row(
            kv_row, list(range(n_parent_full, nb)), own
        )
        entry = aliased + own
        if self._index.put(ids, (n, entry)):
            self.stats["stores"] += 1
        else:
            self.pool.free_blocks(entry)

    def clear(self) -> None:
        self._index.clear()  # on_evict releases every entry's blocks
