"""Device half of the paged KV subsystem: pool-shaped cache arrays plus
the jitted page-table gather / block-scatter programs.

The pool reuses the existing functional cache layout with the BATCH axis
repurposed as the block axis: `model.init_kv(L, pool_blocks, block_tokens)`
yields `[L, N_blocks, block_tokens, KVH, Hd]` leaves (quantized caches
bring their scale leaves along for free, since every op here is a
jax.tree.map).  Composition with the engines:

- **gather** builds the contiguous per-slot view the existing decode
  programs (`apply_window` -> `write_kv`/`cached_attend`) consume: one
  `pool[:, ids]` take per leaf — `batched_gather_cache`'s trick applied to
  the block axis — reshaped to `[L, slots, nb*bt, ...]`.  Unallocated
  table entries clamp to block 0; their rows sit at positions the causal
  mask excludes, so exp() zeroes them EXACTLY and the result is
  bit-identical to the dense path.
- **scatter** writes back only the blocks a step actually touched (the
  block-append write replacing dense `write_kv` persistence): the touched
  rows are sliced out of the dense view and `.at[:, phys].set` into the
  pool, with the pool buffers DONATED so XLA updates in place.

Scatter widths are bucketed to powers of two (padding repeats the last
triple — duplicate scatters of identical content are deterministic) so
the compiled-program set stays bounded, the same discipline as the
engines' chunk buckets.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dnet_tpu.kv.paged import PagedKVConfig
from dnet_tpu.obs.jit import instrument_jit


def _bucket_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class BlockStore:
    """Pool-shaped KV arrays + cached gather/scatter programs."""

    def __init__(
        self,
        model,
        n_layers: int,
        cfg: PagedKVConfig,
        kv_dtype: str,
        quant_bits: int = 0,
        session_tokens: int = 0,
    ) -> None:
        self.cfg = cfg
        self.block_tokens = cfg.block_tokens
        self.kv = model.init_kv(
            n_layers, cfg.pool_blocks, cfg.block_tokens, kv_dtype,
            quant_bits=quant_bits, rotating=False,
        )
        for leaf in jax.tree.leaves(self.kv):
            if leaf.shape[1] != cfg.pool_blocks or leaf.shape[2] != cfg.block_tokens:
                # a model with per-kind cache shapes cannot repurpose the
                # batch axis as blocks
                raise NotImplementedError(
                    "paged KV needs the flat [L, B, S, ...] cache layout; "
                    f"got leaf shape {leaf.shape}"
                )
        if session_tokens:
            # the pool probe alone cannot catch rotating-SWA models: their
            # ring buffers collapse to uniform leaves when rotating=False,
            # but the SESSION caches the engines gather into / commit from
            # (init_kv rotating=True, the default) carry W-wide ring halves
            # whose slots are position MOD W — block geometry over absolute
            # positions would silently commit the wrong rows.  Probe the
            # session layout and refuse anything non-slot-addressed.
            probe = model.init_kv(
                n_layers, 1, session_tokens, kv_dtype, quant_bits=quant_bits
            )
            if jax.tree.structure(probe) != jax.tree.structure(self.kv):
                raise NotImplementedError(
                    "paged KV needs session caches with the pool's tree "
                    "structure (per-kind cache layouts stay dense)"
                )
            for leaf in jax.tree.leaves(probe):
                if leaf.shape[1] != 1 or leaf.shape[2] != session_tokens:
                    raise NotImplementedError(
                        "paged KV needs slot-addressed max_seq session "
                        f"caches; got session leaf shape {leaf.shape} "
                        "(rotating ring buffers stay dense)"
                    )
        bt = self.block_tokens

        @jax.named_scope("kv_gather")
        def gather(pool, ids):
            """ids [slots, nb] int32 -> dense [L, slots, nb*bt, ...]."""

            def one(p):
                g = p[:, ids]  # [L, slots, nb, bt, ...]
                L, s, nb = g.shape[:3]
                return g.reshape(L, s, nb * bt, *g.shape[4:])

            return jax.tree.map(one, pool)

        @jax.named_scope("kv_scatter")
        def scatter(pool, dense, slot_idx, block_idx, phys):
            """Write dense blocks (slot_idx[k], block_idx[k]) -> pool[phys[k]]."""

            def one(p, d):
                L, s, S = d.shape[:3]
                blk = d.reshape(L, s, S // bt, bt, *d.shape[3:])[
                    :, slot_idx, block_idx
                ]  # [L, K, bt, ...]
                return p.at[:, phys].set(blk)

            return jax.tree.map(one, pool, dense)

        @jax.named_scope("kv_append")
        def append(pool, rows, phys, off):
            """Write one new token row per slot straight into its physical
            block: rows leaves [L, slots, KVH, Hd] -> pool[:, phys[s],
            off[s]].  Inactive lanes pass phys == pool_blocks — PAST the
            block axis, so mode="drop" discards the write (a negative
            sentinel would WRAP to block N-1 and clobber a live block
            before drop semantics ever applied).  The ragged decode
            path's replacement for the whole dense round-trip: the
            step's ONLY cache write."""

            def one(p, r):
                return p.at[:, phys, off].set(r.astype(p.dtype), mode="drop")

            return jax.tree.map(one, pool, rows)

        # instrumented: a page-table geometry leak re-tracing these per
        # step shows as climbing dnet_jit_compiles_total{fn=kv_*} (gather
        # widths are pow2-bucketed by the engines, so the compiled-program
        # set stays bounded — see BatchedEngine._table_ids)
        self._gather = instrument_jit(jax.jit(gather), "kv_gather")
        self._scatter = instrument_jit(
            jax.jit(scatter, donate_argnums=(0,)), "kv_scatter"
        )
        self._append = instrument_jit(
            jax.jit(append, donate_argnums=(0,)), "kv_append"
        )

    # ---- ops ----------------------------------------------------------
    def gather(self, ids: np.ndarray) -> dict:
        """Contiguous [L, slots, nb*bt, ...] view of the tables in `ids`
        ([slots, nb], -1/unallocated entries already clamped to 0)."""
        return self._gather(self.kv, jnp.asarray(ids, dtype=jnp.int32))

    def gather_row(self, blocks: List[int], width_tokens: int) -> dict:
        """One sequence's blocks as a [L, 1, width_tokens, ...] dense row
        (padded with clamped block 0 beyond the table — rows the causal
        mask excludes)."""
        bt = self.block_tokens
        assert width_tokens % bt == 0
        ids = np.zeros((1, width_tokens // bt), dtype=np.int32)
        ids[0, : len(blocks)] = blocks
        return self.gather(ids)

    def scatter(
        self,
        dense: dict,
        triples: List[Tuple[int, int, int]],
    ) -> None:
        """Persist touched blocks: triples of (slot, logical_block, phys).
        Pads to a power-of-two width by repeating the last triple."""
        if not triples:
            return
        K = _bucket_pow2(len(triples))
        padded = list(triples) + [triples[-1]] * (K - len(triples))
        slot_idx = jnp.asarray([t[0] for t in padded], dtype=jnp.int32)
        block_idx = jnp.asarray([t[1] for t in padded], dtype=jnp.int32)
        phys = jnp.asarray([t[2] for t in padded], dtype=jnp.int32)
        self.kv = self._scatter(self.kv, dense, slot_idx, block_idx, phys)

    def append_rows(self, rows: dict, phys, off) -> None:
        """Ragged-decode block append: one new token row per slot, written
        in place (donated pool buffers).  rows leaves [L, slots, KVH, Hd]
        (the step program's stacked per-layer k/v outputs); phys/off
        [slots] int32 physical block + in-block offset; phys ==
        pool_blocks (out of range, NOT negative) = skip this lane."""
        self.kv = self._append(
            self.kv, rows,
            jnp.asarray(phys, dtype=jnp.int32),
            jnp.asarray(off, dtype=jnp.int32),
        )

    def commit_row(
        self,
        kv_row: dict,
        logical_blocks: List[int],
        phys_blocks: List[int],
    ) -> None:
        """Persist blocks of a single-sequence dense row ([L, 1, S, ...]):
        logical block index i of the row -> pool block phys_blocks[i]."""
        self.scatter(
            kv_row,
            [(0, lb, pb) for lb, pb in zip(logical_blocks, phys_blocks)],
        )

