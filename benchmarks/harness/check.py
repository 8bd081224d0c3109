"""The comparison that decides `correct`: the SERVED path against the plain
reference, on the run's own weights.

One seeded prompt, longer than a prefill chunk, is sent through the real
endpoint greedily with `logprobs`: the server prefills it in chunks and
decodes `steps` tokens through its cache.  The reference then runs the whole
sequence (prompt + the tokens the server chose) in one float32 pass, and at
every generated position the server's top log-probabilities are compared
with the reference's at the same token ids.  Log-probabilities, not sampled
tokens: with random weights the largest logit changes on rounding.
"""

from __future__ import annotations

import random
from pathlib import Path
from typing import Dict

import aiohttp

from benchmarks.harness.traffic import prompt_text
from benchmarks.harness.weights import reference_module, token_id


async def served_logprobs(url: str, model: str, ids, steps: int, top: int) -> dict:
    body = {
        "model": model, "prompt": prompt_text(ids), "max_tokens": steps,
        "temperature": 0.0, "logprobs": top, "stream": False,
    }
    async with aiohttp.ClientSession() as s:
        async with s.post(url + "/v1/completions", json=body) as resp:
            if resp.status != 200:
                raise RuntimeError(f"check request: HTTP {resp.status} {await resp.text()}")
            return await resp.json()


async def compare(url: str, model: str, model_dir: Path, cfg: dict, check: dict, seed: int) -> Dict:
    """Returns {"max_err", "mean_err", "tolerance", "mean_tolerance", "ok",
    "positions", "values"}."""
    import jax
    import numpy as np

    rng = random.Random(f"dnet-bench:check:{int(seed)}")
    vocab = cfg["vocab_size"]
    ids = [rng.randrange(1, vocab) for _ in range(int(check["prompt_tokens"]))]
    steps, top = int(check["decode_steps"]), int(check.get("top_logprobs", 20))
    resp = await served_logprobs(url, model, ids, steps, top)
    choice = resp["choices"][0]
    lp = choice["logprobs"]
    chosen = [token_id(t) for t in lp["tokens"]]
    if len(chosen) != steps or resp["usage"]["prompt_tokens"] != len(ids):
        raise RuntimeError(
            f"check request: asked {steps} tokens after {len(ids)}, got "
            f"{len(chosen)} after {resp['usage']['prompt_tokens']}"
        )
    # position P-1+j predicts generated token j: feed prompt + all but the last
    seq = ids + chosen[:-1]
    ref = reference_module(cfg["model_type"])
    logits = ref.logits(model_dir, cfg, seq, last=steps)
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    worst, total, n = 0.0, 0.0, 0
    for j, tops in enumerate(lp["top_logprobs"]):
        entries = dict(tops)
        entries[lp["tokens"][j]] = lp["token_logprobs"][j]
        for word, got in entries.items():
            err = abs(float(got) - float(want[j, token_id(word)]))
            worst, total, n = max(worst, err), total + err, n + 1
    tol, mean = float(check["tolerance"]), total / max(n, 1)
    # the mean catches what moves every value a little (int8 weights), the
    # largest what moves one position a lot; a config may leave the mean out
    mean_tol = float(check.get("mean_tolerance", tol))
    return {"max_err": worst, "mean_err": mean, "tolerance": tol, "mean_tolerance": mean_tol,
            "ok": worst <= tol and mean <= mean_tol,
            "positions": steps, "values": n}
