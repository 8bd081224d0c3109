"""Network address helpers (reference: src/dnet/utils/network.py)."""

from __future__ import annotations

import socket
from typing import Iterable


LOOPBACK_HOSTS = ("127.0.0.1", "localhost", "::1")


def primary_ip(peer_hosts: Iterable[str] = ()) -> str:
    """Best-effort address peers can reach us on.

    If every peer is loopback, loopback is correct.  Otherwise use the
    UDP-connect trick against the first non-loopback peer (no packets sent)
    to find the outbound interface address.
    """
    peers = [h for h in peer_hosts if h]
    non_loop = [h for h in peers if h not in LOOPBACK_HOSTS]
    if peers and not non_loop:
        return "127.0.0.1"
    target = non_loop[0] if non_loop else "8.8.8.8"
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect((target, 80))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
