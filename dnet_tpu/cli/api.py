"""`dnet-api` entry point: the API (head) node.

Reference analog: src/cli/api.py. Grows with the build; currently parses args
and validates config so the console script is functional from day one.
"""

from __future__ import annotations

import argparse
import sys

from dnet_tpu.config import configure_compile_cache, get_settings
from dnet_tpu.utils.logger import setup_logger


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dnet-api", description=__doc__)
    s = get_settings()
    p.add_argument("--host", default=s.api.host)
    p.add_argument("--http-port", type=int, default=s.api.http_port)
    p.add_argument("--grpc-port", type=int, default=s.api.grpc_port)
    p.add_argument("--hostfile", default="", help="static discovery hostfile")
    p.add_argument("--model", default="", help="model to load at startup (path or id)")
    p.add_argument("--models-dir", default="", help="override DNET_API_MODELS_DIR")
    p.add_argument(
        "--mesh",
        default="",
        help="in-slice single-program serving, e.g. 'pp=2,tp=2,sp=2' (ICI fast path; sp = sequence-parallel KV / ring attention)",
    )
    p.add_argument(
        "--discovery", choices=["udp", "none"], default="none",
        help="discover shards over UDP broadcast instead of a hostfile",
    )
    p.add_argument("--udp-port", type=int, default=58899)
    p.add_argument("--udp-target", default="255.255.255.255",
                   help="announce target (loopback broadcast for single-host)")
    p.add_argument("--cluster", default="default",
                   help="cluster token scoping UDP discovery membership")
    p.add_argument("--tui", action="store_true", help="live Rich terminal dashboard")
    p.add_argument(
        "--weight-quant-bits", type=int, default=None, choices=[0, 4, 8],
        help="int4/int8 weight-only serving (default DNET_API_WEIGHT_QUANT_BITS)",
    )
    p.add_argument(
        "--auto-recover", action="store_true",
        help="on shard failure, re-solve the ring over healthy shards and reload",
    )
    p.add_argument(
        "--batch-slots", type=int, default=None,
        help="continuous batching: N KV slots share one batched decode "
        "program (default DNET_API_BATCH_SLOTS)",
    )
    return p


def main(argv: list[str] | None = None) -> int:
    cache_dir = configure_compile_cache()
    args = build_parser().parse_args(argv)
    log = setup_logger(role="api")
    log.info("compile cache: %s", cache_dir)
    log.info("dnet-api starting on %s:%d (grpc %d)", args.host, args.http_port, args.grpc_port)
    from dnet_tpu.api.server import serve  # noqa: PLC0415

    serve(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
