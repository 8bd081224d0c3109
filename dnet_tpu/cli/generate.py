"""`dnet-generate`: offline SPMD batch generation.

The lockstep counterpart of the HTTP server: every process of a multi-host
pod runs THIS SAME command with its own DNET_MESH_PROCESS_ID, joins the
distributed runtime (parallel/mesh.ensure_distributed), builds the same
mesh engine over the global device set, and dispatches identical programs —
so the collectives line up by construction (the property request-driven
serving cannot guarantee; api/server.py refuses that combination and points
here).  Single-process it is a plain offline batch generator over the
local/mesh engine.

Input: one prompt per line (text file or - for stdin).
Output: JSONL {"prompt", "text", "tokens", "tok_s"} per line (process 0
only on multi-host pods — every process computes identical results).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from dnet_tpu.config import configure_compile_cache, get_settings
from dnet_tpu.utils.logger import setup_logger


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dnet-generate", description=__doc__)
    s = get_settings()
    p.add_argument("--model", required=True, help="checkpoint path or catalog id")
    p.add_argument("--prompts", default="-", help="file with one prompt per line (- = stdin)")
    p.add_argument("--output", default="-", help="JSONL output path (- = stdout)")
    p.add_argument("--max-tokens", type=int, default=128)
    p.add_argument("--max-seq", type=int, default=s.api.max_seq_len)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--param-dtype", default=s.api.param_dtype)
    p.add_argument(
        "--mesh", default="",
        help="e.g. 'pp=2,tp=2' — spans ALL hosts' chips on a joined pod",
    )
    p.add_argument("--raw", action="store_true",
                   help="feed prompts verbatim (no chat template)")
    p.add_argument(
        "--spec", type=int, default=0, metavar="L",
        help="speculative decoding lookahead (greedy only; 0 = off) — "
        "works on the local engine and on --mesh engines alike",
    )
    p.add_argument(
        "--draft", default="", metavar="MODEL",
        help="draft MODEL for speculation (checkpoint path or catalog id; "
        "needs --spec; local engine only — without it drafts come from "
        "prompt-lookup)",
    )
    return p


def main(argv=None) -> int:
    configure_compile_cache()
    args = build_parser().parse_args(argv)
    setup_logger("api")
    s = get_settings()

    # join the pod BEFORE any backend use; each process sees the global mesh
    from dnet_tpu.parallel.mesh import ensure_distributed, parse_mesh

    dist = ensure_distributed(
        s.mesh.coordinator, s.mesh.num_processes, s.mesh.process_id
    )
    if dist and s.mesh.num_processes > 1 and args.prompts == "-":
        # stdin diverges across pod launchers (workers usually get EOF): a
        # process reading fewer prompts dispatches fewer collectives and
        # the pod deadlocks — require a shared file instead
        print(
            "multi-process pods need --prompts <file> (identical on every "
            "host); stdin is not lockstep-safe",
            file=sys.stderr,
        )
        return 2

    import jax

    from dnet_tpu.api.model_manager import resolve_model_dir
    from dnet_tpu.core.types import DecodingParams
    from dnet_tpu.utils.tokenizer import load_tokenizer

    model_dir = resolve_model_dir(args.model, s.api.models_dir)
    if model_dir is None:
        print(f"model {args.model!r} not found", file=sys.stderr)
        return 2

    draft_dir = None
    if args.draft:
        draft_dir = resolve_model_dir(args.draft, s.api.models_dir)
        if draft_dir is None:
            print(f"draft model {args.draft!r} not found", file=sys.stderr)
            return 2
        if args.spec <= 0:
            print("--draft needs --spec L", file=sys.stderr)
            return 2

    mesh_kw = parse_mesh(args.mesh)
    if mesh_kw:
        if draft_dir is not None:
            print(
                "--draft is local-engine only; mesh engines draft by "
                "prompt-lookup", file=sys.stderr,
            )
            return 2
        from dnet_tpu.parallel.engine import MeshEngine

        engine = MeshEngine(
            model_dir,
            pp=mesh_kw.get("pp", 0), tp=mesh_kw.get("tp", 1),
            dp=mesh_kw.get("dp", 1), sp=mesh_kw.get("sp", 1),
            max_seq=args.max_seq, param_dtype=args.param_dtype,
            spec_lookahead=args.spec,
        )
    else:
        from dnet_tpu.core.engine import LocalEngine

        engine = LocalEngine(
            model_dir, max_seq=args.max_seq, param_dtype=args.param_dtype,
            spec_lookahead=args.spec, draft_dir=draft_dir,
        )
    tokenizer = load_tokenizer(model_dir)
    dec = DecodingParams(
        temperature=args.temperature, top_p=args.top_p, seed=args.seed
    )
    eos = set(tokenizer.eos_token_ids)

    src = sys.stdin if args.prompts == "-" else open(args.prompts)
    prompts = [ln.rstrip("\n") for ln in src if ln.strip()]
    if src is not sys.stdin:
        src.close()

    # process 0 writes; the others compute the identical stream in lockstep
    # and must NOT open the (possibly shared) output path — a worker's
    # truncating open would discard process 0's rows
    emit = (not dist) or jax.process_index() == 0
    if not emit:
        out = sys.stdout  # never written to (emit gates every write)
    else:
        out = sys.stdout if args.output == "-" else open(args.output, "w")
    try:
        for i, prompt in enumerate(prompts):
            if args.raw:
                ids = tokenizer.encode(prompt)
            else:
                text = tokenizer.apply_chat_template(
                    [{"role": "user", "content": prompt}]
                )
                ids = tokenizer.encode(text, add_bos=False)
            t0 = time.perf_counter()
            toks = [
                r.token_id
                for r in engine.generate(
                    ids, dec, max_tokens=args.max_tokens,
                    eos_token_ids=eos, nonce=f"gen{i}",
                )
            ]
            dt = time.perf_counter() - t0
            if toks and toks[-1] in eos:
                toks = toks[:-1]
            if emit:
                out.write(json.dumps({
                    "prompt": prompt,
                    "text": tokenizer.decode(toks),
                    "tokens": len(toks),
                    "tok_s": round(len(toks) / max(dt, 1e-9), 2),
                }) + "\n")
                out.flush()
    finally:
        if out is not sys.stdout:
            out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
