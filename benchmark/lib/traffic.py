"""Traffic generation and the arithmetic on what comes back: stratified
draws, arrival schedules, token crediting, percentiles. Pure Python (no
jax, no numpy), so the unit tests run in milliseconds.

Steadiness rule 1 (stratified draws): a length distribution becomes ONE
fixed list of K quantile midpoints; requests are issued in consecutive
blocks of K, each block a seed-shuffled permutation of that list. Every
seed, and every stretch of a window, then carries the same mix of work:
the seed decides order and token ids only.
"""

import math
import random
from statistics import NormalDist

K = 16  # strata per block (pairs of prompt and output length; arrival gaps)


def _midpoints(k):
    return [(i + 0.5) / k for i in range(k)]


def lognormal_strata(spec, k=K):
    """K quantile midpoints of a log-normal ``{"median", "sigma", "min",
    "max"}``, clipped and rounded to whole tokens, ascending."""
    nd = NormalDist()
    out = []
    for q in _midpoints(k):
        v = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
        out.append(int(round(min(max(v, spec["min"]), spec["max"]))))
    return out


def length_pairs(traffic, k=K):
    """The K (prompt, output) pairs of a serving mix. Outputs are paired
    with prompts through a fixed stride so the two are uncorrelated but
    the pairing never depends on the seed."""
    prompts = lognormal_strata(traffic["prompt_tokens"], k)
    outputs = lognormal_strata(traffic["output_tokens"], k)
    limit = traffic.get("max_total_tokens")
    pairs = []
    for i, p in enumerate(prompts):
        o = outputs[(7 * i + 3) % k]
        if limit is not None and p + o > limit:
            raise ValueError(f"pair ({p}, {o}) exceeds max_total_tokens "
                             f"{limit}")
        pairs.append((p, o))
    return pairs


def exponential_gaps(rate_rps, k=K):
    """K quantile midpoints of the exponential arrival gap at
    ``rate_rps``, rescaled so one block of K arrivals spans exactly
    K / rate seconds (the midpoints cut the tail, which would otherwise
    raise the rate by a few percent)."""
    raw = [-math.log(1.0 - q) for q in _midpoints(k)]
    scale = k / (rate_rps * sum(raw))
    return [g * scale for g in raw]


def blocks(strata, seed, stream):
    """Endless iterator over ``strata`` in consecutive seed-shuffled
    blocks. ``stream`` separates independent uses of one seed."""
    rnd = random.Random(f"{seed}:{stream}")
    while True:
        block = list(strata)
        rnd.shuffle(block)
        yield from block


def arrival_times(rate_rps, seed, until_s):
    """Due times (seconds from the start of load) of an open loop up to
    ``until_s``: the stratified gaps, accumulated."""
    out, t = [], 0.0
    for gap in blocks(exponential_gaps(rate_rps), seed, "gaps"):
        t += gap
        if t >= until_s:
            return out
        out.append(t)


def token_ids(seed, index, n, vocab):
    """The ``n`` prompt tokens of request ``index``: the same seed gives
    the same ids; 0 is left out (padding id in the engine's buckets)."""
    rnd = random.Random(f"{seed}:tok:{index}")
    return rnd.choices(range(1, vocab), k=n)


# ------------------------------------------------------------ arithmetic
def percentile(values, q):
    """The q-th percentile (0..100), linear between order statistics —
    numpy's default — or None for an empty list."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def credited_tokens(records, t_open, t_close):
    """Steadiness rule 2: a request's prompt tokens are credited at the
    arrival of its first streamed token (its prefill is then done) and
    each output token at its own arrival; only arrivals inside
    [t_open, t_close) count, whatever window the request started or
    ends in. ``records``: dicts with ``prompt_tokens`` and ``arrivals``
    (a list of ``(time, n_tokens)`` frames). Returns (prompt, output)."""
    prompt = output = 0
    for r in records:
        for j, (t, n) in enumerate(r["arrivals"]):
            if t_open <= t < t_close:
                output += n
                if j == 0:
                    prompt += r["prompt_tokens"]
    return prompt, output


def credited_by_fifth(records, t_open, t_close):
    """Credited tokens (prompt + output) in each fifth of the window: a
    cycle here means the ramp was too short to dephase the clients."""
    step = (t_close - t_open) / 5.0
    return [sum(credited_tokens(records, t_open + i * step,
                                t_open + (i + 1) * step))
            for i in range(5)]


def token_gaps_ms(records, t_open, t_close):
    """Gaps between consecutive streamed frames of each request whose
    later frame arrived inside the window, in ms (the inter-token
    latency a client sees; one frame carries one decode round)."""
    out = []
    for r in records:
        arr = r["arrivals"]
        for (t0, _), (t1, _) in zip(arr, arr[1:]):
            if t_open <= t1 < t_close:
                out.append((t1 - t0) * 1000.0)
    return out
