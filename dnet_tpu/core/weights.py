"""Weight streaming: host-DRAM layer store + windowed HBM residency.

The TPU translation of the reference's no-memory-ceiling subsystem
(SURVEY.md §2.1): Apple-UMA disk<->GPU swapping becomes host-DRAM<->HBM
`jax.device_put` streaming.

- HostLayerStore  ≙ utils/model.py + utils/repack.py: lazy mmap-backed
  per-layer host params (model-mapped, pre-transposed), with an optional
  on-disk repack cache keyed by model + layer-set hash (repack.py:175-217)
  so restarts skip the transpose work.
- WeightCache     ≙ core/memory/weight_cache.py: bounded HBM residency
  (max_resident layers), thread-safe load-once via per-layer Futures
  (weight_cache.py:69-196), ref-counted pin/release, LRU eviction of
  unpinned layers (235-259), async prefetch on a thread pool overlapping
  compute (offload.py:395-421).
- plan_policy     ≙ shard/policies/__init__.py:20-65 thresholds.
"""

from __future__ import annotations

import hashlib
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from dnet_tpu.utils.logger import get_logger

log = get_logger()


# ---- policy planning -------------------------------------------------------


@dataclass(frozen=True)
class PolicyPlan:
    name: str  # "fit" | "offload" | "sliding_fit"
    window_size: int
    residency: int  # max layers resident in HBM

    @property
    def streams_weights(self) -> bool:
        return self.name != "fit"


def plan_policy(
    local_count: int, window_size: int = 0, residency_size: int = 0
) -> PolicyPlan:
    """Reference thresholds (policies/__init__.py:20-65):
    residency < window        -> sliding_fit (evict inside the window)
    window >= local layers    -> fit (everything resident)
    else                      -> offload (window-at-a-time streaming)
    """
    w = window_size or local_count
    n = residency_size or local_count
    if w >= local_count and n >= local_count:
        return PolicyPlan("fit", local_count, local_count)
    if n < w:
        return PolicyPlan("sliding_fit", w, max(n, 1))
    return PolicyPlan("offload", w, min(max(n, w), local_count))


# ---- host store ------------------------------------------------------------


class HostLayerStore:
    """Model-mapped per-layer host params, lazily materialized.

    Repack cache: mapped (renamed + transposed + dtype-cast) layers are
    written once as .npz under
      <cache_dir>/<model-tag>/<sha1(layers)[:10]>/layer_<i>.npz
    and mmap-loaded on later runs (reference repack.py:98-217).
    """

    def __init__(
        self,
        ckpt,
        model,
        param_dtype: str = "bfloat16",
        repack_dir: Optional[str | Path] = None,
        weight_quant_bits: int = 0,
        weight_quant_group: int = 0,
    ) -> None:
        self.ckpt = ckpt
        self.model = model
        self.param_dtype = np.dtype(
            __import__("ml_dtypes").bfloat16 if param_dtype == "bfloat16" else param_dtype
        )
        self.weight_quant_bits = weight_quant_bits
        self.weight_quant_group = weight_quant_group
        self._cache: Dict[int, Dict[str, np.ndarray]] = {}
        self._lock = threading.Lock()
        self.repack_path: Optional[Path] = None
        if repack_dir is not None:
            tag = Path(ckpt.dir).name
            key = hashlib.sha1(
                f"v3:{param_dtype}:wq{weight_quant_bits}g{weight_quant_group}:"
                f"{','.join(map(str, model.layers))}".encode()
            ).hexdigest()[:10]
            self.repack_path = Path(repack_dir).expanduser() / tag / key
            self.repack_path.mkdir(parents=True, exist_ok=True)

    def _cast(self, tree: Dict[str, object]) -> Dict[str, object]:
        out: Dict[str, object] = {}
        for k, v in tree.items():
            if isinstance(v, dict):  # quantized leaf: q stays int, s is typed
                out[k] = self._cast(v)
            elif np.issubdtype(v.dtype, np.floating) and v.dtype != self.param_dtype:
                out[k] = v.astype(self.param_dtype)
            else:
                out[k] = v
        return out

    def layer_host(self, layer: int):
        """ONE layer's host params shaped as a single-layer window pytree
        (model.wrap_offload_layer), ready for device placement."""
        with self._lock:
            if layer in self._cache:
                return self._cache[layer]
        params = self.model.wrap_offload_layer(self._load_layer_flat(layer))
        with self._lock:
            self._cache[layer] = params
        return params

    def _load_layer_flat(self, layer: int) -> Dict[str, np.ndarray]:
        if self.repack_path is not None:
            f = self.repack_path / f"layer_{layer}.npz"
            if f.is_file():
                z = np.load(f)
                return _unflatten({k: _bf16_view(z[k]) for k in z.files})
        t0 = time.perf_counter()
        mapped = self.model.map_layer(self.ckpt.load_layer_raw(layer))
        if self.weight_quant_bits:
            # quantize the RAW checkpoint values (before any lossy cast) so
            # fit and offload policies serve bit-identical quantized weights
            mapped = self.model.quantize_layer(
                mapped,
                self.weight_quant_bits,
                scale_dtype=self.param_dtype,
                group_size=self.weight_quant_group,
            )
        mapped = self._cast(mapped)
        log.info(
            "[PROFILE] host-load layer %d in %.1fms", layer, (time.perf_counter() - t0) * 1e3
        )
        if self.repack_path is not None:
            f = self.repack_path / f"layer_{layer}.npz"
            tmp = f.with_suffix(".tmp.npz")
            # bf16 is not npz-native; save raw bytes views.  Quantized leaf
            # dicts flatten to "name::q" / "name::s" entries.
            flat = _flatten(mapped)
            np.savez(tmp, **{k: v.view(np.uint16) if v.dtype == np.dtype("bfloat16") else v for k, v in flat.items()})
            tmp.rename(f)
        return mapped

    def prefetch_disk(self, layers: Sequence[int]) -> None:
        """Kick native page-cache readahead for layers about to materialize
        (disk->DRAM half of the prefetch; host->HBM is WeightCache's).
        Repacked layers read from .npz instead — skip those spans."""
        ckpt = self.ckpt
        if ckpt is None or not hasattr(ckpt, "prefetch_layer"):
            return
        for layer in layers:
            with self._lock:
                if layer in self._cache:
                    continue
            if (
                self.repack_path is not None
                and (self.repack_path / f"layer_{layer}.npz").is_file()
            ):
                continue
            ckpt.prefetch_layer(layer)

    def drop_host(self, layer: int) -> None:
        with self._lock:
            self._cache.pop(layer, None)
        # evicted spans can leave the page cache too (re-faultable); repacked
        # layers never touched the safetensors map, nothing to release
        ckpt = self.ckpt
        if (
            ckpt is not None
            and hasattr(ckpt, "release_layer")
            and not (
                self.repack_path is not None
                and (self.repack_path / f"layer_{layer}.npz").is_file()
            )
        ):
            ckpt.release_layer(layer)


# ---- HBM weight cache -------------------------------------------------------


class WeightCache:
    """Bounded HBM residency with load-once futures + LRU eviction."""

    def __init__(
        self,
        store: HostLayerStore,
        max_resident: int,
        prefetch_workers: int = 2,
        device=None,
        put_fn=None,
    ) -> None:
        self.store = store
        self.max_resident = max_resident
        self.device = device
        # custom host->device placement (host pytree -> device pytree):
        # mesh-backed shards stream each layer as tp/sp-SHARDED device_puts
        # (parallel/shard_mesh.py) instead of whole-layer single-chip copies
        self.put_fn = put_fn
        self._lock = threading.Lock()
        self._futures: Dict[int, Future] = {}  # layer -> Future[device params]
        self._resident: Dict[int, dict] = {}  # layer -> device params
        self._refs: Dict[int, int] = {}
        self._last_used: Dict[int, float] = {}
        self._pool = ThreadPoolExecutor(
            max_workers=prefetch_workers, thread_name_prefix="prefetch"
        )
        self.stats = {"loads": 0, "hits": 0, "evictions": 0}

    # -- internal ------------------------------------------------------------
    def _load_to_device(self, layer: int) -> dict:
        host = self.store.layer_host(layer)
        t0 = time.perf_counter()
        if self.put_fn is not None:
            dev = self.put_fn(jax.tree.map(_bf16_view, host))
        else:
            dev = jax.tree.map(
                lambda v: jax.device_put(_bf16_view(v), self.device), host
            )
        jax.block_until_ready(dev)  # dnetlint: disable=DL005 load-time weight-upload fence, not on the decode path
        log.info(
            "[PROFILE] HBM-load layer %d in %.1fms", layer, (time.perf_counter() - t0) * 1e3
        )
        return dev

    def _ensure_future(self, layer: int) -> Future:
        """Caller must hold the lock. Dedups concurrent loads via one Future
        per layer (reference weight_cache.py:89-104)."""
        fut = self._futures.get(layer)
        if fut is None:
            fut = self._pool.submit(self._load_to_device, layer)
            self._futures[layer] = fut
            self.stats["loads"] += 1
        return fut

    def _evict_to_budget(self, incoming: int = 1) -> None:
        """Caller must hold the lock. Evict LRU unpinned layers until the
        incoming load fits the residency budget."""
        while len(self._resident) + incoming > self.max_resident:
            candidates = [
                (self._last_used.get(l, 0.0), l)
                for l in self._resident
                if self._refs.get(l, 0) == 0
            ]
            if not candidates:
                return  # everything pinned; caller may exceed budget briefly
            _, victim = min(candidates)
            del self._resident[victim]
            self._refs.pop(victim, None)
            self._last_used.pop(victim, None)
            self.stats["evictions"] += 1

    # -- public --------------------------------------------------------------
    def prefetch(self, layers: Sequence[int]) -> None:
        """Schedule async host->HBM loads (no waiting)."""
        # start disk->page-cache readahead for the whole window first: the
        # executor materializes layers one at a time, the native worker
        # pulls the later ones off disk concurrently
        if hasattr(self.store, "prefetch_disk"):
            self.store.prefetch_disk(layers)
        with self._lock:
            for layer in layers:
                if layer not in self._resident:
                    self._ensure_future(layer)

    def get(self, layer: int, pin: bool = True) -> dict:
        """Blocking: returns device params, loading if needed; pins by ref."""
        with self._lock:
            if layer in self._resident:
                self.stats["hits"] += 1
                if pin:
                    self._refs[layer] = self._refs.get(layer, 0) + 1
                self._last_used[layer] = time.monotonic()
                return self._resident[layer]
            fut = self._ensure_future(layer)
        try:
            dev = fut.result()  # outside the lock: others can proceed
        except Exception:
            # drop the failed future so a retry can load fresh (a cached
            # failure would poison the layer forever)
            with self._lock:
                if self._futures.get(layer) is fut:
                    self._futures.pop(layer, None)
            raise
        with self._lock:
            if layer not in self._resident:
                self._evict_to_budget(incoming=1)
                self._resident[layer] = dev
            self._futures.pop(layer, None)
            if pin:
                self._refs[layer] = self._refs.get(layer, 0) + 1
            self._last_used[layer] = time.monotonic()
            return self._resident[layer]

    def release(self, layers: Sequence[int]) -> None:
        with self._lock:
            for layer in layers:
                if self._refs.get(layer, 0) > 0:
                    self._refs[layer] -= 1

    def evict(self, layers: Sequence[int]) -> None:
        """Proactive eviction of unpinned layers (reference 261-290)."""
        with self._lock:
            for layer in layers:
                if self._refs.get(layer, 0) == 0:
                    self._resident.pop(layer, None)
                    self._last_used.pop(layer, None)

    def resident_layers(self) -> List[int]:
        with self._lock:
            return sorted(self._resident)

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)
        with self._lock:
            self._resident.clear()
            self._futures.clear()
            self._refs.clear()


def _bf16_view(v: np.ndarray) -> np.ndarray:
    """npz repack stores bf16 as uint16; view back when shapes match."""
    if v.dtype == np.uint16:
        import ml_dtypes

        return v.view(ml_dtypes.bfloat16)
    return v


def _flatten(tree: Dict[str, object]) -> Dict[str, np.ndarray]:
    """One-level nesting ({"wq": {"q": ..., "s": ...}}) -> "wq::q" keys."""
    flat: Dict[str, np.ndarray] = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            for k2, v2 in v.items():
                flat[f"{k}::{k2}"] = v2
        else:
            flat[k] = v
    return flat


def _unflatten(flat: Dict[str, np.ndarray]) -> Dict[str, object]:
    out: Dict[str, object] = {}
    for k, v in flat.items():
        if "::" in k:
            k1, _, k2 = k.partition("::")
            out.setdefault(k1, {})[k2] = v
        else:
            out[k] = v
    return out
