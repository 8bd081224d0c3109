"""Which implementation each Pallas dispatcher picked, counted per process.

Fifteen dispatchers choose between a Pallas kernel and a jnp path at trace
time: causal prefill (`ops/flash_attention.py`), split-K decode
(`ops/flash_decode.py`), ragged paged attend and its absorbed twin over
latent entries (`ops/paged_attention.py`), the
two hop-codec kernels (`compression/ops.py`), power retention's decode
step and prefill chunk (`ops/retention.py`), the gated delta rule's (`ops/gated_delta.py`),
lightning linear attention's (`ops/lightning.py`) and block-sparse
attention's index, decode read and prefill (`ops/sparse_attention.py`).
The backend half of
that choice lives here, so that it is made one way: a TPU backend runs the
Mosaic-compiled kernel and nothing else; DNET_FLASH_INTERPRET=1 selects
interpret mode on a CPU backend (tier-1) and is an error on a TPU one.

Every selection is booked into `SELECTIONS`, which `/health` reports as
its `kernels` block.  Selections happen while tracing, so a count is the
number of traced programs that embedded the kernel, not of launches.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

#: what a dispatcher can resolve to
IMPLS = ("pallas", "interpret", "emulate", "dense")
#: the dispatchers, by the name `/health` reports them under
KERNELS = (
    "flash_prefill",
    "flash_decode",
    "paged_attend",
    "paged_attend_latent",
    "column_norms",
    "column_select",
    "retention_step",
    "retention_chunk",
    "gdn_step",
    "gdn_chunk",
    "lightning_step",
    "lightning_chunk",
    "sparse_index",
    "paged_attend_sparse",
    "flash_prefill_sparse",
)


class KernelSelections:
    """Per-kernel counts of the implementation selected, plus the operand
    shapes that went `dense` (ineligible for the kernel)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._counts = {k: dict.fromkeys(IMPLS, 0) for k in KERNELS}
            self._dense: dict = {k: set() for k in KERNELS}

    def record(self, kernel: str, impl: str, shapes=None) -> None:
        with self._lock:
            self._counts[kernel][impl] += 1
            if impl == "dense" and shapes is not None:
                self._dense[kernel].add(tuple(tuple(s) for s in shapes))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                k: {
                    **self._counts[k],
                    "dense_shapes": sorted(list(map(list, s)) for s in self._dense[k]),
                }
                for k in KERNELS
            }


SELECTIONS = KernelSelections()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_backend() -> Optional[str]:
    """`pallas` on a TPU backend, `interpret` under DNET_FLASH_INTERPRET=1
    elsewhere, None where no Pallas kernel can run (callers go dense)."""
    from dnet_tpu.config import env_flag

    interpret = env_flag("DNET_FLASH_INTERPRET")
    if on_tpu():
        if interpret:
            raise RuntimeError(
                "DNET_FLASH_INTERPRET=1 on a TPU backend: interpret mode is "
                "the CPU test override and must never stand in for the "
                "Mosaic-compiled kernels on the chip; unset it"
            )
        return "pallas"
    return "interpret" if interpret else None


def device_report() -> dict:
    """The `/health` `device` block: what JAX says this process runs on."""
    devs = jax.devices()
    per_device = []
    for d in devs:
        stats = d.memory_stats() or {}
        per_device.append(
            {"id": d.id, "bytes_in_use": int(stats.get("bytes_in_use", 0))}
        )
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
        "devices": per_device,
    }
