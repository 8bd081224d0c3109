"""`dnet-shard` entry point: a shard (worker) node.

Reference analog: src/cli/shard.py.
"""

from __future__ import annotations

import argparse
import sys

from dnet_tpu.config import configure_compile_cache, get_settings
from dnet_tpu.utils.logger import setup_logger


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dnet-shard", description=__doc__)
    s = get_settings()
    p.add_argument("--host", default=s.shard.host)
    p.add_argument("--http-port", type=int, default=s.shard.http_port)
    p.add_argument("--grpc-port", type=int, default=s.shard.grpc_port)
    p.add_argument("--queue-size", type=int, default=s.shard.queue_size)
    p.add_argument("--shard-name", default=s.shard.name)
    p.add_argument(
        "--discovery", choices=["udp", "none"], default="udp",
        help="announce this shard over UDP broadcast (native lib)",
    )
    p.add_argument("--udp-port", type=int, default=58899)
    p.add_argument("--udp-target", default="255.255.255.255",
                   help="announce target (loopback broadcast for single-host)")
    p.add_argument("--cluster", default="default",
                   help="cluster token scoping UDP discovery membership")
    p.add_argument("--tui", action="store_true", help="live Rich terminal dashboard")
    return p


def main(argv: list[str] | None = None) -> int:
    cache_dir = configure_compile_cache()
    args = build_parser().parse_args(argv)
    log = setup_logger(role="shard")
    log.info("compile cache: %s", cache_dir)
    log.info(
        "dnet-shard %s starting on %s:%d (grpc %d)",
        args.shard_name or "<unnamed>",
        args.host,
        args.http_port,
        args.grpc_port,
    )
    from dnet_tpu.shard.server import serve  # noqa: PLC0415

    serve(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
