"""On-device token sampling: temperature / top-k / top-p / min-p + logprobs.

One jitted function serves every request: all decoding knobs are traced
scalars (not static args), so changing temperature or top_p never recompiles
— the fix for the reference's "end-shard sampling under jit" hard part
(SURVEY.md §7).  Greedy vs stochastic is a `jnp.where` select.  The filters
(top-k with a *traced* k, top-p, min-p, min_tokens_to_keep) each keep a
prefix of the row's descending order, and a prefix is fixed by its last
entry: `filter_keep` finds that (value, index) pair by a threshold search
over the row, counting and weighing the entries above each threshold, and
cuts in vocabulary order by comparing every entry with the pair: no sort, no
ranks, no vocabulary-sized gather.  Functionality mirrors
the reference's mlx_lm-based Sampler (src/dnet/core/decoding/sampler.py:14-65).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dnet_tpu.core.types import DecodingParams
from dnet_tpu.obs.phases import SCOPE_SAMPLE

MAX_TOP_LOGPROBS = 20  # static upper bound (OpenAI API max); request slices host-side
# static per-request logit_bias capacity — the full OpenAI API cap (300
# keys), so no valid client request is rejected; the scatter cost scales
# with this width but stays trivial next to a vocab-sized logits row
MAX_LOGIT_BIAS = 300


def encode_logit_bias(bias) -> tuple:
    """dict {token_id: bias} -> fixed-width (ids [MAX], vals [MAX]) numpy
    arrays, id -1 padding (scattered with mode=drop).  None = no bias."""
    ids = np.full((MAX_LOGIT_BIAS,), -1, dtype=np.int32)
    vals = np.zeros((MAX_LOGIT_BIAS,), dtype=np.float32)
    if bias:
        if len(bias) > MAX_LOGIT_BIAS:
            raise ValueError(
                f"logit_bias supports at most {MAX_LOGIT_BIAS} entries; "
                f"got {len(bias)}"
            )
        for i, (t, b) in enumerate(sorted(bias.items())):
            ids[i] = int(t)
            vals[i] = float(b)
    return ids, vals


class SampleParams(NamedTuple):
    """Traced sampling knobs (all jnp scalars inside jit)."""

    temperature: jnp.ndarray
    top_p: jnp.ndarray
    top_k: jnp.ndarray  # int32; 0 disables
    min_p: jnp.ndarray
    repetition_penalty: jnp.ndarray  # 1.0 disables
    # filters may never shrink the candidate set below this many tokens
    # (reference: min_tokens_to_keep, core/decoding/config.py:4-14, passed
    # through make_sampler); 1 = only the argmax is guaranteed
    min_tokens_to_keep: jnp.ndarray  # int32
    # OpenAI logit_bias: fixed-width (ids, additive values); -1 ids drop.
    # The reference carries the field in its DecodingConfig but never
    # applies it (src/dnet/api/models.py:70 "NOTE: unused") — here it bites.
    bias_ids: jnp.ndarray  # [MAX_LOGIT_BIAS] int32
    bias_vals: jnp.ndarray  # [MAX_LOGIT_BIAS] f32

    @classmethod
    def from_decoding(cls, d: DecodingParams) -> "SampleParams":
        """One request's knobs as a HOST pytree (numpy leaves): it goes
        into a jitted program as arguments, so building it dispatches
        nothing and no leaf is a device array before the call."""
        ids, vals = encode_logit_bias(getattr(d, "logit_bias", None))
        return cls(
            temperature=np.float32(d.temperature),
            top_p=np.float32(d.top_p),
            top_k=np.int32(d.top_k),
            min_p=np.float32(d.min_p),
            repetition_penalty=np.float32(d.repetition_penalty),
            min_tokens_to_keep=np.int32(d.min_tokens_to_keep),
            bias_ids=ids,
            bias_vals=vals,
        )


class SamplePlan(NamedTuple):
    """STATIC sampling shape, derived host-side from DecodingParams.

    The traced-knob design (SampleParams) means one program serves every
    request — but it also means every decode step pays for machinery most
    requests never use: a threshold search over the row, a few dozen counting
    passes, for the filters (`filter_keep`), a log_softmax + top_k(20) for
    logprobs, a scatter for the bias.  The plan collapses the unused machinery at trace time; the handful
    of plan combinations bound the number of compiled variants, and knobs
    *within* a plan stay traced (a temperature change still never
    recompiles).  What the machinery costs on the chip is in PERF.md
    (sections 5 and 6) and the ledger, per cell; no figure is kept here.
    """

    greedy: bool  # temperature <= 0: token = argmax, no sampling machinery
    filters: bool  # any of top_p < 1 / top_k > 0 / min_p > 0 active
    logprobs: bool  # request wants logprob + top-logprob outputs
    penalty: bool  # repetition_penalty != 1
    bias: bool = False  # logit_bias present: scatter-add before everything

    @classmethod
    def from_decoding(cls, d: DecodingParams) -> "SamplePlan":
        return cls(
            greedy=d.temperature <= 0.0,
            filters=(d.top_p < 1.0) or (d.top_k > 0) or (d.min_p > 0.0),
            logprobs=bool(d.logprobs),
            penalty=d.repetition_penalty != 1.0,
            bias=bool(getattr(d, "logit_bias", None)),
        )


# the everything-on plan: default for callers that keep all knobs traced
# (bias included: its ids default to -1 = dropped, so unbiased requests
# through FULL_PLAN still sample identically)
FULL_PLAN = SamplePlan(
    greedy=False, filters=True, logprobs=True, penalty=True, bias=True
)


class SampleResult(NamedTuple):
    token: jnp.ndarray  # [B] int32
    logprob: jnp.ndarray  # [B] f32, log-softmax of raw logits at token
    top_tokens: jnp.ndarray  # [B, MAX_TOP_LOGPROBS] int32
    top_logprobs: jnp.ndarray  # [B, MAX_TOP_LOGPROBS] f32


def pack_chunk_results(results: SampleResult, with_logprobs: bool) -> jnp.ndarray:
    """Pack a scanned SampleResult ([K, B, ...] leaves) into ONE f32 array
    for a single device->host transfer per decode chunk (token ids are exact
    in f32 for V < 2**24).  Shared by LocalEngine's decode_chunk and the
    mesh ring chunk program (parallel/ring.py)."""
    if with_logprobs:
        return jnp.concatenate(
            [
                results.token[..., None].astype(jnp.float32),
                results.logprob[..., None],
                results.top_tokens.astype(jnp.float32),
                results.top_logprobs,
            ],
            axis=-1,
        )
    return results.token[..., None].astype(jnp.float32)


# bits of a threshold settled by one read of the row: 2**n - 1 thresholds are
# tried together (what a pass costs on the chip: PERF.md section 6)
_BITS_A_PASS = 2


def _largest(holds, bits: int, like: jnp.ndarray) -> jnp.ndarray:
    """The largest threshold t of `bits` bits (uint32, shaped and sharded as
    `like`) at which `holds(t)` is true, for a `holds` that is true at 0 and
    never true above a threshold where it is false.  Built from the top bit
    down, `_BITS_A_PASS` at a time: a fixed trip count, so a per-lane `vmap`
    batches the loop."""
    step = _BITS_A_PASS
    passes = -(-bits // step)

    def settle(i, t):
        shift = step * (passes - 1 - i).astype(jnp.uint32)
        tried = [holds(t | (jnp.uint32(j) << shift)) for j in range(1, 2**step)]
        return t | (sum(h.astype(jnp.uint32) for h in tried) << shift)

    return jax.lax.fori_loop(0, passes, settle, jnp.zeros_like(like, jnp.uint32))


def filter_keep(scaled: jnp.ndarray, params: SampleParams) -> jnp.ndarray:
    """The filters' kept set for temperature-scaled logits [B, V], as a
    boolean mask in vocabulary order, from a threshold search over the row.

    Top-k, top-p, min-p and min_tokens_to_keep each keep a prefix of the
    row's descending order, so together they keep a prefix, and a prefix is
    fixed by ONE (value, index) pair: its last entry.  Finding it needs no
    order, only how many entries stand above a threshold and what they
    weigh, both step functions of the threshold: the pair's value is
    searched bit by bit over a monotone integer key of the float, its index
    bit by bit inside the group of entries that share the value, and the
    mask compares every entry with the pair.  Nothing is sorted, ranked or
    gathered.

    Order of equal values (the common case: logits leave `lm_project` in
    bf16): among equal values the HIGHER index stands first, and a cut
    inside such a group keeps its higher indices.

    top_p >= 1 keeps the whole row; below 1 the prefix ends at the first
    position whose exclusive cumulative probability reaches top_p.  (A mask
    `cumsum - p < top_p` taken entry by entry is not a prefix at
    top_p = 1.0: on a peaked row the float cumsum reaches 1.0 early and
    wobbles around it, which drops scattered far-tail entries.)
    """
    B, V = scaled.shape
    # a single row goes without its batch axis: vmapped a lane at a time
    # (core/batch.py) every pass then reads [slots, V], not [slots, 1, V]
    x = scaled[0] if B == 1 else scaled
    ids = jax.lax.broadcasted_iota(jnp.uint32, x.shape, x.ndim - 1)

    def count(where):
        return jnp.sum(where, axis=-1, keepdims=True, dtype=jnp.int32)

    # ascending uint32 key of the float32 value: the sign bit flipped for
    # non-negatives, every bit for negatives (-inf, a banned token, last)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    # how many entries a prefix may hold: top-k (k == 0 -> all) and min-p
    # (probability >= min_p * max prob: monotone in the value, so the count
    # of such entries IS their prefix length); never fewer than
    # min_tokens_to_keep (>= 1: the argmax always survives) or over the row
    probs = jax.nn.softmax(x, axis=-1)
    pmax = jnp.max(probs, axis=-1, keepdims=True)
    most = jnp.minimum(
        jnp.where(params.top_k > 0, params.top_k, V),
        count(probs >= params.min_p * pmax),
    )
    least = jnp.clip(params.min_tokens_to_keep, 1, V)
    no_top_p = params.top_p >= 1.0

    def mass(where):
        return jnp.sum(jnp.where(where, probs, 0.0), axis=-1, keepdims=True)

    def too_many(where):
        """More entries, or more weight, than may stand ahead of a kept entry?"""
        ahead, before = count(where), mass(where)
        over = (ahead >= most) | ((before >= params.top_p) & ~no_top_p)
        return over & (ahead >= least)

    # the cut's value: the largest key with too many entries at or above it
    cut_key = _largest(lambda t: too_many(key >= t), 32, pmax)

    # inside the group that shares it, exclusive cumulative probabilities
    # step by the group's one probability (0 at temperature ~ 0: the
    # division then says all of the group or none, as it should)
    above, group = key > cut_key, key == cut_key
    ahead, before, size = count(above), mass(above), count(group)
    cut = jnp.max(jnp.where(group, x, -jnp.inf), axis=-1, keepdims=True)
    each = jnp.max(jnp.where(group, probs, 0.0), axis=-1, keepdims=True)
    by_mass = jnp.where(
        before < params.top_p, jnp.ceil((params.top_p - before) / each), 0.0
    )
    by_mass = jnp.where(no_top_p, V, jnp.clip(by_mass, 0, V)).astype(jnp.int32)
    n = jnp.clip(
        jnp.maximum(jnp.minimum(most - ahead, by_mass), least - ahead), 1, size
    )

    # the cut's index: the largest with n of the group at or above it
    cut_id = _largest(
        lambda i: count(group & (ids >= i)) >= n, max(V - 1, 1).bit_length(), pmax
    )
    keep = (x > cut) | ((x == cut) & (ids >= cut_id))
    return keep.reshape(scaled.shape)


@jax.named_scope(SCOPE_SAMPLE)
def sample(
    logits: jnp.ndarray,
    params: SampleParams,
    key: jax.Array,
    token_counts: Optional[jnp.ndarray] = None,
    plan: Optional[SamplePlan] = None,
) -> SampleResult:
    """logits [B, V] -> sampled tokens with logprobs.

    Filter semantics (matching mlx_lm's make_sampler composition used by the
    reference): repetition penalty over seen tokens, scale by temperature,
    keep top-k, keep smallest prefix with cumulative prob >= top_p, drop
    tokens below min_p * p_max, sample.  temperature == 0 -> greedy argmax.

    `plan` statically skips machinery a request doesn't use (see SamplePlan);
    the default FULL_PLAN preserves the everything-traced behavior.  Fields
    a plan disables come back as zeros (shapes are stable across plans).
    """
    if plan is None:
        plan = FULL_PLAN
    if plan.bias:
        # additive logit_bias before every other knob: greedy argmax,
        # filters, and reported logprobs all see the biased distribution
        # (OpenAI semantics).  Padded (-1) AND out-of-vocab ids scatter a
        # zero — jax would otherwise wrap/clip them onto real vocab rows
        # and silently force/ban an unrelated token.
        V = logits.shape[-1]
        in_vocab = (params.bias_ids >= 0) & (params.bias_ids < V)
        vals = jnp.where(in_vocab, params.bias_vals, 0.0)
        ids = jnp.clip(params.bias_ids, 0, V - 1)
        logits = logits.astype(jnp.float32).at[:, ids].add(vals)
    if plan.penalty and token_counts is not None:
        logits = apply_repetition_penalty(
            logits, token_counts, params.repetition_penalty
        )
    B, V = logits.shape

    if plan.greedy:
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        temp = jnp.maximum(params.temperature, 1e-6)
        scaled = logits.astype(jnp.float32) / temp
        if plan.filters:
            masked = jnp.where(filter_keep(scaled, params), scaled, -jnp.inf)
        else:
            masked = scaled

        gumbel = jax.random.gumbel(key, masked.shape, dtype=jnp.float32)
        stochastic = jnp.argmax(masked + gumbel, axis=-1)
        greedy = jnp.argmax(logits, axis=-1)
        token = jnp.where(params.temperature <= 0.0, greedy, stochastic).astype(jnp.int32)

    if plan.logprobs:
        raw_logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logprob = jnp.take_along_axis(raw_logprobs, token[:, None], axis=-1)[:, 0]
        n_top = min(MAX_TOP_LOGPROBS, V)
        # A single row goes in without its batch axis: vmapped a lane at a
        # time (core/batch.py) the operand is then [slots, V], for which the
        # TPU has a TopK kernel; [slots, 1, V] it compiles as a full sort.
        rows = raw_logprobs[0] if B == 1 else raw_logprobs
        top_lp, top_ids = (x.reshape(B, n_top) for x in jax.lax.top_k(rows, n_top))
        if n_top < MAX_TOP_LOGPROBS:  # tiny-vocab tests: pad to the static width
            pad = MAX_TOP_LOGPROBS - n_top
            top_lp = jnp.pad(top_lp, ((0, 0), (0, pad)), constant_values=-jnp.inf)
            top_ids = jnp.pad(top_ids, ((0, 0), (0, pad)))
        top_ids = top_ids.astype(jnp.int32)
    else:
        logprob = jnp.zeros((B,), jnp.float32)
        top_ids = jnp.zeros((B, MAX_TOP_LOGPROBS), jnp.int32)
        top_lp = jnp.zeros((B, MAX_TOP_LOGPROBS), jnp.float32)
    return SampleResult(token, logprob, top_ids, top_lp)


@partial(jax.jit, static_argnames=())
def sample_jit(logits: jnp.ndarray, params: SampleParams, key: jax.Array) -> SampleResult:
    return sample(logits, params, key)


def apply_repetition_penalty(
    logits: jnp.ndarray, token_counts: jnp.ndarray, penalty: jnp.ndarray
) -> jnp.ndarray:
    """CTRL-style repetition penalty from a per-vocab count buffer.

    token_counts: [B, V] int32 counts of generated/context tokens.
    penalty 1.0 = disabled.
    """
    seen = token_counts > 0
    lf = logits.astype(jnp.float32)
    penalized = jnp.where(lf > 0, lf / penalty, lf * penalty)
    return jnp.where(seen, penalized, lf).astype(logits.dtype)
