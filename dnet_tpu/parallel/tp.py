"""Intra-shard tensor parallelism over a ("batch", "model") mesh.

ROADMAP item 3's TP half (DNET_TP=N, default 1 = today's behavior): a ring
shard's attention heads and MLP matrices shard across its host-local chips
with NamedSharding over a two-axis ("batch", "model") mesh — the classic
cross-replica weight-sharding layout (PAPERS.md, arxiv 2004.13336) — while
activations keep hopping host-to-host over the gRPC ring.  A v5litepod-4
host stops serving as a 1-chip hop: the solver places it as ONE mesh slice
(parallel/solver.py mesh-slice placement) and its whole window runs tp=4.

Three pieces live here:

- :func:`place_presharded` — weights load PRE-SHARDED: each chip's slice
  of each tensor is cut from the host (mmap-backed) array, cast, and
  uploaded individually, then assembled with
  ``jax.make_array_from_single_device_arrays``.  Neither the host cast
  buffer nor any single chip ever materializes a full tensor — load peak
  is 1/N per chip.  MeshShardEngine's loader routes through this too.
- the ("batch", "model") spec rules — the same column/row-parallel name
  sets as parallel/mesh.py, re-expressed on the 2-axis mesh; the KV cache
  (dense [L, B, S, KVH, Hd] AND pool-shaped [L, N, bt, KVH, Hd]) shards
  on the HEAD axis, so per-chip views keep the exact layout the PR 12
  ragged kernel reads — it runs per chip unchanged.
- :class:`TpEngine` — MeshShardEngine with the substrate hooks overridden:
  2-axis mesh, pre-sharded specs, and the per-layer collectives routed
  through the quantizable seam (parallel/tp_collectives.py) as a
  :class:`~dnet_tpu.parallel.tp_collectives.TpAxis`, so
  ``DNET_TP_COLLECTIVE=q8`` shrinks the intra-shard interconnect the way
  the PR 14 wire codec shrank the ring hops.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dnet_tpu.parallel.mesh import (
    _COL_PARALLEL,
    _EXPERT_SHARDED,
    _EXPERT_VECTORS,
    _HEAD_VECTORS,
    _ROW_PARALLEL,
)
from dnet_tpu.parallel.shard_mesh import MeshShardEngine
from dnet_tpu.parallel.tp_collectives import (
    MODE_LOSSLESS,
    TpAxis,
    collective_bytes,
    observe_collective_bytes,
    probe_collective_ms,
    resolve_collective_mode,
)
from dnet_tpu.utils.logger import get_logger

log = get_logger()

AXIS_BATCH, AXIS_MODEL = "batch", "model"


def tp_enabled_degree() -> int:
    """The configured DNET_TP degree (1 = off, today's behavior)."""
    from dnet_tpu.config import get_settings

    return max(int(get_settings().tp.tp), 1)


def build_tp_mesh(
    tp: int, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """A (batch=1, model=tp) mesh over the shard's local chips."""
    devices = list(devices if devices is not None else jax.devices())
    if tp > len(devices):
        raise ValueError(
            f"tp={tp} needs {tp} devices, have {len(devices)}"
        )
    grid = np.array(devices[:tp]).reshape(1, tp)
    return Mesh(grid, (AXIS_BATCH, AXIS_MODEL))


# ---- ("batch", "model") sharding rules ------------------------------------
# Same name sets as the 4-axis mesh (parallel/mesh.py); the stacked layer
# axis is UNSHARDED here (the pipeline is the gRPC ring outside the mesh)
# and tensor splits ride the "model" axis.


def tp_param_spec(name: str) -> P:
    if name in _COL_PARALLEL:
        return P(None, None, AXIS_MODEL)
    if name in _ROW_PARALLEL:
        return P(None, AXIS_MODEL, None)
    if name in _HEAD_VECTORS:
        return P(None, AXIS_MODEL)
    if name in _EXPERT_SHARDED:
        return P(None, AXIS_MODEL, None, None)
    if name in _EXPERT_VECTORS:
        return P(None, AXIS_MODEL, None)
    return P()  # norms, routers, kind scalars: replicate


def tp_window_specs(window_params: Dict) -> Dict:
    """Spec pytree for a stacked window (two-level segment layouts too)."""
    out: Dict = {}
    for k, v in window_params.items():
        if k in ("dense", "moe", "a", "b") and isinstance(v, dict):
            out[k] = {kk: tp_param_spec(kk) for kk in v}
        else:
            out[k] = tp_param_spec(k)
    return out


def tp_kv_spec() -> P:
    """KV sharded on the HEAD axis over "model" — one spec for BOTH rank-5
    cache layouts: the dense [L, B, S, KVH, Hd] session cache (B rides the
    size-1 batch axis) and the pool-shaped [L, N_blocks, bt, KVH, Hd]
    paged layout, whose per-chip view keeps exactly the shape the PR 12
    ragged kernel's block index map addresses — the kernel runs per chip
    unchanged, each chip attending its own KVH/tp heads."""
    return P(None, None, None, AXIS_MODEL, None)


# ---- pre-sharded placement ------------------------------------------------


def place_presharded(tree, mesh: Mesh, specs, cast=None):
    """Place a host pytree onto the mesh WITHOUT materializing full
    tensors: for every leaf, each device's slice is cut from the host
    array (a view into the mmap-backed checkpoint), optionally cast —
    slice-sized copies only — uploaded to its device, and the global
    array assembled from the per-device pieces.

    ``specs`` mirrors the tree one level deep (the window_param_specs
    layout: name -> spec, with segment dicts nested one more level); a
    spec covers every leaf of its subtree, which is how quantized weight
    dicts ({codes, scales}) inherit their tensor's split.
    """

    def place_leaf(a, spec: P):
        arr = np.asarray(a)
        sharding = NamedSharding(mesh, spec)
        shards = []
        for dev, idx in sharding.addressable_devices_indices_map(
            arr.shape
        ).items():
            sl = arr[idx]
            if cast is not None:
                sl = cast(sl)
            shards.append(jax.device_put(np.ascontiguousarray(sl), dev))
        return jax.make_array_from_single_device_arrays(
            arr.shape, sharding, shards
        )

    def place_subtree(subtree, spec):
        if isinstance(spec, dict):
            return {k: place_subtree(subtree[k], spec[k]) for k in subtree}
        return jax.tree.map(lambda leaf: place_leaf(leaf, spec), subtree)

    if not isinstance(specs, dict):
        return place_subtree(tree, specs)
    return {k: place_subtree(v, specs[k]) for k, v in tree.items()}


class TpEngine(MeshShardEngine):
    """A ring shard's compute core, tensor-parallel over ("batch","model").

    MeshShardEngine with the substrate hooks overridden: same jitted-fn
    surface, same Session contract, same ShardCompute hot loop — the
    window math runs SPMD over the 2-axis mesh with the per-layer
    all-reduces routed through the quantizable collective seam.  Greedy
    streams under the lossless mode are byte-identical to tp=1 (the
    parity contract tests/subsystems/test_tp_parity.py pins through the
    real HTTP server).
    """

    def __init__(
        self,
        model_dir: str | Path,
        layers: Sequence[int],
        tp: int = 1,
        devices: Optional[Sequence] = None,
        collective: str = "",
        collective_group_size: int = 0,
        **kwargs,
    ) -> None:
        if tp < 1:
            raise ValueError(f"tp={tp} must be positive")
        if kwargs.pop("sp", 1) != 1:
            raise ValueError(
                "TpEngine is tensor-parallel only; sequence parallelism "
                "stays on the shard_map substrate (parallel/shard_mesh.py)"
            )
        from dnet_tpu.config import get_settings

        devices = list(devices if devices is not None else jax.devices())
        w = get_settings().tp
        self.collective_mode = resolve_collective_mode(
            collective or w.tp_collective, devices=devices[:tp]
        )
        self.collective_group_size = int(
            collective_group_size or w.tp_group_size
        )
        self._coll_books = {"all_reduce": 0, "all_gather": 0}
        # grandparent init on purpose: MeshShardEngine.__init__ would
        # build the 4-axis mesh; everything else it does is LocalEngine's
        self.tp, self.sp = tp, 1
        self.mesh = build_tp_mesh(tp, devices)
        from dnet_tpu.core.engine import LocalEngine

        LocalEngine.__init__(
            self,
            model_dir,
            layers=list(layers),
            shard_mode=True,
            **kwargs,
        )
        from dnet_tpu.obs import metric

        metric("dnet_tp_degree").set(float(tp))
        if tp > 1:
            probe_collective_ms(
                self.mesh, AXIS_MODEL, self.config.hidden_size,
                self.param_dtype, self.collective_mode,
                self.collective_group_size,
            )

    def _build_fns(self) -> None:
        """The inherited program builders, with every jitted TP entry
        point instrumented under ONE declared label: a shape leak in the
        sharded window programs shows up as a climbing
        dnet_jit_compiles_total{fn="tp_window"} instead of a mystery
        per-hop latency cliff (the obs/jit.py contract; the flow lint's
        DL021/DL022 jit model seeds its wrapper set from JIT_FNS)."""
        from dnet_tpu.obs.jit import instrument_jit

        super()._build_fns()
        for attr in ("_hidden", "_hidden_round", "_embed_window",
                     "_hidden_tail", "_forward", "_decode", "_decode_chunk",
                     "_spec_step"):
            fn = getattr(self, attr, None)
            if fn is not None:
                setattr(self, attr, instrument_jit(fn, "tp_window"))

    # ---- substrate hooks ---------------------------------------------
    def _tp_axis(self):
        return TpAxis(
            AXIS_MODEL,
            mode=self.collective_mode,
            group_size=self.collective_group_size,
        )

    def _sp_axis(self):
        return None

    def _certify_axes(self):
        # nothing is sharded over the size-1 batch axis, so x never turns
        # varying over it and there is nothing to certify back
        return ()

    def _window_specs_of(self, tree):
        return tp_window_specs(tree)

    def _kv_pspec(self):
        return tp_kv_spec()

    def _place_window(self, host_tree):
        return place_presharded(
            host_tree, self.mesh, self._window_specs_of(host_tree),
            cast=self._np_cast,
        )

    def _load_params(self) -> None:
        # head divisibility is a LOAD-time contract: a tp that does not
        # divide the q/kv head counts would shard a head across chips
        cfg = self.config
        heads = cfg.num_attention_heads or 0
        kv_heads = cfg.num_key_value_heads or heads
        for kind, n in (("attention", heads), ("kv", kv_heads)):
            if self.tp > 1 and n and n % self.tp != 0:
                raise ValueError(
                    f"tp={self.tp} does not divide {kind} heads ({n}); "
                    f"the solver clamps tp_degree to a divisor — pass one"
                )
        super()._load_params()

    # ---- collective byte accounting (host side, per dispatch) ---------
    def observe_step_collectives(self, tokens: int = 1) -> None:
        """Book the analytic interconnect bytes one window pass paid:
        2 all-reduces per layer over [B, T, D] activations (the models'
        out-proj and down-proj seams).  Called by ShardCompute after each
        dispatched frame — pure shape math, no device syncs."""
        if self.tp <= 1:
            return
        n_elem = max(tokens, 1) * self.config.hidden_size
        eb = np.dtype(self.param_dtype).itemsize
        nbytes = 2 * len(self.model.layers) * collective_bytes(
            "all_reduce", self.collective_mode, self.tp, n_elem, eb,
            self.collective_group_size,
        )
        observe_collective_bytes("all_reduce", nbytes)
