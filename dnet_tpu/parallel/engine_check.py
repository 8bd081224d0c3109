"""MeshEngine's logits against LocalEngine's, on one prompt, one process.

    python -m dnet_tpu.parallel.engine_check --model <dir> --mesh pp=2,tp=2

Both engines load the same checkpoint through the real loader; the local
one computes on device 0, the mesh one over the first pp*tp*dp*sp devices.
Compared: the last-position logits of one prefill.  Greedy TEXT is not
compared — on bf16 hardware with random weights near-ties flip between two
correct engines.  `chip_smoke.py` runs this in its *mesh4* phase.  Prints
one JSON line; exits non-zero over tolerance.
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def main(argv=None) -> int:
    from dnet_tpu.config import configure_compile_cache, get_settings

    configure_compile_cache()
    p = argparse.ArgumentParser(prog="python -m dnet_tpu.parallel.engine_check")
    p.add_argument("--model", required=True)
    p.add_argument("--mesh", required=True, help="e.g. pp=2,tp=2")
    p.add_argument("--max-seq", type=int, default=get_settings().api.max_seq_len)
    # Relative to the largest logit.  Weights and activations are bf16 and
    # the tp split sums each row-parallel matmul in two halves before the
    # psum, so every layer's output rounds differently from the one-chip
    # engine's: ~2^-9 per rounding, two per layer, ~1% after 16 layers.  A
    # misplaced shard, a dropped psum or a skipped stage is off by O(100%).
    p.add_argument("--tolerance", type=float, default=0.05)
    args = p.parse_args(argv)

    from dnet_tpu.core.engine import LocalEngine
    from dnet_tpu.ops.kernel_select import device_report
    from dnet_tpu.parallel.engine import MeshEngine
    from dnet_tpu.parallel.mesh import parse_mesh
    from dnet_tpu.utils.tokenizer import ByteTokenizer

    ids = ByteTokenizer().encode("The ring passes activations between chips.")
    local = LocalEngine(args.model, max_seq=args.max_seq)
    want = np.asarray(local.prefill("check", ids), np.float32)[0]
    local.end_session("check")
    del local

    mesh = MeshEngine(args.model, max_seq=args.max_seq, **parse_mesh(args.mesh))
    got = np.asarray(mesh.prefill("check", ids), np.float32)[0]
    holding = [d["bytes_in_use"] for d in device_report()["devices"]]
    diff = float(np.max(np.abs(got - want)))
    scale = max(float(np.max(np.abs(want))), 1.0)
    ok = bool(np.all(np.isfinite(got)) and diff <= args.tolerance * scale)
    print(json.dumps({
        "ok": ok, "max_abs_diff": diff, "tolerance": args.tolerance * scale,
        "logit_abs_max": float(np.max(np.abs(want))),
        "argmax_agree": bool(int(np.argmax(got)) == int(np.argmax(want))),
        "vocab": int(want.shape[-1]), "mesh": dict(mesh.mesh.shape),
        "bytes_in_use": holding,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
