"""Paged KV-cache subsystem: block-pool allocation, page tables,
copy-on-write prefix sharing, and free-block admission.

Host half: `paged.py` (BlockPool / PageTable / KVPoolExhausted).
Device half: `store.py` (pool-shaped arrays, attended in place, + the
row gather / block commit programs of prefix restore and adoption; and the
store of the third kind, a recurrent state entry a lane, which has no host
half: no blocks to manage; and the store that holds a pool and state
entries side by side for a model that mixes the two).
Sharing: `prefix.py` (PagedPrefixCache over the same pool).

The batched engine's KV layout wherever the model and the cache allow it
(core/batch.py: kv_layout); dense slots serve the rest.
"""

from dnet_tpu.kv.paged import (
    BlockPool,
    KVPoolExhausted,
    PagedKVConfig,
    PageTable,
    ceil_div,
    window_blocks,
    window_first_block,
)
from dnet_tpu.kv.prefix import PagedPrefixCache
from dnet_tpu.kv.store import HybridStore, KindStore, StateStore

__all__ = [
    "BlockPool",
    "HybridStore",
    "KVPoolExhausted",
    "KindStore",
    "PagedKVConfig",
    "PagedPrefixCache",
    "PageTable",
    "StateStore",
    "ceil_div",
    "window_blocks",
    "window_first_block",
]
