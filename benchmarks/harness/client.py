"""Streaming clients that stamp every token.

Copied in spirit from dnet_tpu/loadgen/client.py (one SSE stream -> one
row), with what the window arithmetic needs and that one lacks: the arrival
time of EVERY token, the due time of the request, and streams that may be
cut at the window's end.  Drives `/v1/completions` with a raw prompt, so
the prompt's token count is exact.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field
from typing import List, Optional

import aiohttp

from benchmarks.harness.traffic import Planned, prompt_text

clock = time.perf_counter


@dataclass
class StreamRow:
    client: int
    seq: int
    asked: int  # max_tokens
    due: float  # when the request was due to be sent
    sent: float = 0.0  # when it was sent
    status: int = 0
    stamps: List[float] = field(default_factory=list)  # one per token
    finished: bool = False  # the stream ended with [DONE]
    error: str = ""

    @property
    def failed(self) -> bool:
        """A response other than 200, a broken stream, or a finished stream
        with another token count than asked.  A stream cut by the window's
        end is neither finished nor failed."""
        return bool(self.error) or (self.finished and len(self.stamps) != self.asked)


def request_body(p: Planned, model: str, sampling: dict) -> dict:
    body = {
        "model": model,
        "prompt": prompt_text(p.prompt_ids),
        "max_tokens": p.max_tokens,
        "stream": True,
        "seed": p.seed,
    }
    body.update(sampling)
    return body


async def stream_one(
    session: aiohttp.ClientSession, url: str, body: dict, row: StreamRow
) -> None:
    """Send one request now and stamp its tokens into `row`.  Errors become
    the row's `error`; cancellation (the window's end) passes through."""
    row.sent = clock()
    try:
        async with session.post(url + "/v1/completions", json=body) as resp:
            row.status = resp.status
            if resp.status != 200:
                row.error = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                now = clock()
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    row.finished = True
                    return
                chunk = json.loads(payload)
                if chunk.get("error"):
                    row.error = str(chunk["error"])[:200]
                    return
                for choice in chunk.get("choices") or ():
                    # one word per token under the benchmark tokenizer
                    row.stamps.extend([now] * len((choice.get("text") or "").split()))
            row.error = "stream ended without [DONE]"
    except asyncio.CancelledError:
        raise
    except Exception as exc:  # transport failure: a failed row, not a crash
        row.error = f"{type(exc).__name__}: {exc}"[:200]


class LoadDriver:
    """Runs a plan against a server until stopped; keeps every row."""

    def __init__(self, url: str, model: str, plans: List[List[Planned]], traffic: dict):
        self.url, self.model = url, model
        self.plans, self.traffic = plans, traffic
        self.rows: List[StreamRow] = []
        self._tasks: List[asyncio.Task] = []
        self._session: Optional[aiohttp.ClientSession] = None
        self.t_start = 0.0
        self.wrapped = 0  # clients that reached the end of their list

    async def start(self) -> None:
        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=None),
            connector=aiohttp.TCPConnector(limit=0),
        )
        self.t_start = clock()
        self._tasks = [asyncio.ensure_future(self._closed(mine)) for mine in self.plans]

    async def _closed(self, mine: List[Planned]) -> None:
        due = self.t_start
        i = 0
        while True:
            p = mine[i % len(mine)]
            row = StreamRow(p.client, i, p.max_tokens, due)
            self.rows.append(row)
            await self._send(p, row)
            if row.error:
                await asyncio.sleep(0.5)  # do not spin on a failing server
            due = clock()  # the next request is due when this answer ended
            i += 1
            self.wrapped += i == len(mine)

    async def _send(self, p: Planned, row: StreamRow) -> None:
        body = request_body(p, self.model, self.traffic.get("sampling", {}))
        await stream_one(self._session, self.url, body, row)

    def ramped(self) -> bool:
        """Every client has a first token and `warm_ticks` further tokens
        on some stream."""
        need = 1 + int(self.traffic.get("warm_ticks", 4))
        seen = {}
        for row in self.rows:
            seen[row.client] = max(seen.get(row.client, 0), len(row.stamps))
        return len(seen) == len(self.plans) and all(n >= need for n in seen.values())

    def lateness(self, t0: float, t1: float) -> List[float]:
        """Sent minus due (seconds) for every request sent in the window."""
        return [r.sent - r.due for r in self.rows if r.sent and t0 < r.sent <= t1]

    async def stop(self) -> None:
        tasks = list(self._tasks)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        if self._session is not None:
            await self._session.close()
