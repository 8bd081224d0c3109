"""Paged KV-cache subsystem: block-pool allocation, page tables,
copy-on-write prefix sharing, and free-block admission.

Host half: `paged.py` (BlockPool / PageTable / KVPoolExhausted).
Device half: `store.py` (pool-shaped arrays + gather/scatter programs).
Sharing: `prefix.py` (PagedPrefixCache over the same pool).

Enabled per-engine via DNET_KV_PAGED=1 (config.KVSettings); the dense
preallocated path stays the default.
"""

from dnet_tpu.kv.paged import (
    BlockPool,
    KVPoolExhausted,
    PagedKVConfig,
    PageTable,
    ceil_div,
    paged_enabled,
    ragged_enabled,
    window_blocks,
    window_first_block,
)
from dnet_tpu.kv.prefix import PagedPrefixCache
from dnet_tpu.kv.store import BlockStore, KindStore

__all__ = [
    "BlockPool",
    "BlockStore",
    "KVPoolExhausted",
    "KindStore",
    "PagedKVConfig",
    "PagedPrefixCache",
    "PageTable",
    "ceil_div",
    "paged_enabled",
    "ragged_enabled",
    "window_blocks",
    "window_first_block",
]
