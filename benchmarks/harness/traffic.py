"""The one traffic generator: a mix is a data file, the plan a pure
function of (mix, seed).

Every seed gets the SAME multiset of prompt and answer lengths (evenly
spaced quantiles of the mix's distributions) in another order, and the
lengths are dealt in blocks so that the order cannot change the work much:
within one block of `block` requests every client gets one length from each
of `block` bands of the distribution (short ... long), so every client's
block is about the same work, and the clients' blocks together are exactly
the quantile set.  What the seed decides is which client gets which length
of a band, and the order inside its block.

A mix (benchmarks/traffic/<mix>.json), closed loop (each client sends its
next request when the previous answer ended):

  clients         number of clients
  requests_per_client   length of each client's list; a client that reaches
                  its end starts over (the run says so: its prompts repeat)
  block           requests per client in one dealt block (default: the list)
  prompt_tokens, answer_tokens   {"dist": "fixed" | "uniform" | "loguniform",
                  "min": a, "max": b}
  schedule_seed   optional: lengths, their order and the residual shares come
                  from THIS number and not from --seed, which then decides
                  only token ids, sampling seeds and weights.  Only for a cell
                  whose speed depends on how the lanes' lengths line up so
                  much that no window averages it out (PERF.md section 6)
  sampling        body fields sent with every request (temperature, top_p..)
  residual_life_start   each client's FIRST answer is cut to a seeded share
                  in (0, 1] of its length, so clients start out of phase as
                  they would be in steady state
  warm_ticks      tokens every client must have received beyond its first
                  before the window opens
  ramp_limit_s    the longest the ramp may take
  open_quiet_s, open_quiet_limit_s   the window opens once no token has
                  arrived for open_quiet_s (between two bursts of a batching
                  server, not inside one), or after open_quiet_limit_s at the
                  latest.  Decides only where the window starts
  warm_prompt_tokens    prompt lengths sent one by one before the clients
                  start, so that every prefill shape is compiled in set-up;
                  each asks 2 tokens, the last `warm_answer_tokens` (so that
                  every fused decode width runs once)
  trace_slice_s   length of the profiled slice in a traced run
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from typing import List, Sequence, Tuple


@dataclass(frozen=True)
class Planned:
    client: int
    seq: int
    prompt_ids: Tuple[int, ...]
    max_tokens: int
    seed: int  # the request's sampling seed


def quantiles(dist: dict, n: int) -> List[int]:
    """n evenly spaced quantiles of a length distribution, as whole tokens,
    ascending."""
    kind, lo, hi = dist["dist"], float(dist["min"]), float(dist["max"])
    if kind == "fixed" or lo == hi:
        return [int(round(lo))] * n
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if kind == "uniform":
            v = lo + q * (hi - lo)
        elif kind == "loguniform":
            v = math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
        else:
            raise ValueError(f"unknown dist {kind!r}")
        out.append(max(1, int(round(v))))
    return out


def _rng(seed: int, what: str) -> random.Random:
    # str seeds hash with a stable algorithm; any whole number is fine
    return random.Random(f"dnet-bench:{what}:{int(seed)}")


def deal(dist: dict, clients: int, per: int, block: int, rng: random.Random) -> List[List[int]]:
    """Each client's `per` lengths.  One block: the clients*block quantiles
    cut into `block` bands of `clients` neighbours; a band is shuffled over
    the clients, then each client's block is shuffled."""
    q = quantiles(dist, clients * block)
    mine: List[List[int]] = [[] for _ in range(clients)]
    while len(mine[0]) < per:
        hands: List[List[int]] = [[] for _ in range(clients)]
        for k in range(block):
            band = q[k * clients:(k + 1) * clients]
            rng.shuffle(band)
            for c in range(clients):
                hands[c].append(band[c])
        for c in range(clients):
            rng.shuffle(hands[c])
            mine[c].extend(hands[c])
    return [m[:per] for m in mine]


def residual_shares(clients: int, seed: int) -> List[float]:
    """Each client's share in (0, 1] of its first answer: the residual life
    of a stream met at a random moment.  Evenly spread, order from the seed."""
    shares = [(k + 1) / clients for k in range(clients)]
    _rng(seed, "residual").shuffle(shares)
    return shares


def _ids(rng: random.Random, n: int, vocab: int) -> Tuple[int, ...]:
    return tuple(rng.randrange(1, vocab) for _ in range(n))


def prompt_text(ids: Sequence[int]) -> str:
    """The benchmark tokenizer (weights.write_tokenizer) maps word t<i> to id i."""
    return " ".join(f"t{i}" for i in ids)


def plan(traffic: dict, seed: int, vocab: int) -> List[List[Planned]]:
    """One list of requests per client."""
    clients = int(traffic["clients"])
    per = int(traffic.get("requests_per_client", 8))
    block = int(traffic.get("block", per))
    sched = traffic.get("schedule_seed", seed)  # who sends which lengths, when
    prompts = deal(traffic["prompt_tokens"], clients, per, block, _rng(sched, "prompts"))
    answers = deal(traffic["answer_tokens"], clients, per, block, _rng(sched, "answers"))
    shares = residual_shares(clients, sched) if traffic.get("residual_life_start") else None
    body = _rng(seed, "body")  # what the prompts say
    out: List[List[Planned]] = []
    for c in range(clients):
        mine = []
        for s in range(per):
            max_tokens = answers[c][s]
            if shares is not None and s == 0:
                max_tokens = max(1, math.ceil(max_tokens * shares[c]))
            mine.append(
                Planned(
                    client=c,
                    seq=s,
                    prompt_ids=_ids(body, prompts[c][s], vocab),
                    max_tokens=max_tokens,
                    seed=body.randrange(2**31),
                )
            )
        out.append(mine)
    return out
