"""The arithmetic behind the end-to-end metrics: whole-window rates and
tails over every request. Nothing here drops a sample."""

import math


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks, as numpy's default does. ``values`` may hold
    ``math.inf`` (a request that never answered): it sorts last and
    comes out as itself."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or xs[lo] == xs[hi]:
        return xs[lo]
    if math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def rate(count, seconds):
    """Work over the whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


def ttft_ms(due, first_token, worst):
    """Per-request time to first token in ms, timed from when each
    request was DUE. ``first_token[i]`` is None for a request that
    failed, was refused or never answered: it counts as ``worst`` (s)."""
    return [1e3 * ((f - d) if f is not None else worst)
            for d, f in zip(due, first_token)]


def gaps_ms(token_times):
    """Every gap between consecutive output tokens of every request."""
    out = []
    for ts in token_times:
        out.extend(1e3 * (b - a) for a, b in zip(ts, ts[1:]))
    return out
