"""The one general generator of serving traffic. A mix is a data file
(``benchmark/traffic/<name>.json``, kind ``serve``) of parameters that
belong to the traffic and to no configuration:

    source            the public trace the lengths are taken from
    prompt, answer    {"mean", "min", "max"}: exponential lengths, the
                      law that assumes nothing but a published mean,
                      clipped (every clip is listed under ``cut``)
    shared_prefix     optional {"share", "length", "groups"}: that share of
                      the requests starts with one of ``groups`` fixed
                      prefixes of ``length`` tokens (the mixes with
                      sessions that PERF.md section 7 keeps for later: a
                      later PR can add them as data only)
    base_seed         fixes the arrival times and the lengths of every run

Arrivals are Poisson (exponential gaps) at the rate of the CELL
(``benchmark/cells/<cell>.json``): the rate lies inside 0.7-0.8 of one
configuration's knee and belongs to no mix.

The MIX fixes when each request is due and how long its prompt and its
answer are; ``--seed`` gives the token ids (and, elsewhere, the weights).
So every seed does the same work at the same instants, and the spread
between runs is the system's and not the draw's: a 95th percentile over
a hundred-odd requests is set by the worst burst of its schedule, and a
schedule in another order is another burst (PERF.md section 6 shows the
tails on other ``base_seed``s). The gaps are unit draws divided by the
rate, so a sweep sees one arrival pattern at every rate, and a shorter
horizon is a prefix of a longer one.
"""

import numpy as np


def lengths(rng, spec, n):
    x = np.ceil(rng.exponential(spec["mean"], n))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def serve_schedule(traffic, rate, vocab, max_len, seed, horizon_s):
    """Requests due in [0, horizon_s) at ``rate`` a second: a list of
    dicts ``{"due", "prompt" (int32 ids), "max_new"}`` sorted by due
    time."""
    base = traffic["base_seed"]
    n = int(rate * horizon_s * 1.5) + 64       # more than ever fall inside
    gaps = np.random.default_rng([base, 0]).exponential(1.0, n)
    due = (np.cumsum(gaps) - gaps[0]) / rate
    n = int(np.searchsorted(due, horizon_s))
    if n == len(due):
        raise ValueError(f"{n} unit gaps do not fill {horizon_s} s at "
                         f"{rate}/s")
    prompts = lengths(np.random.default_rng([base, 1]), traffic["prompt"], n)
    answers = lengths(np.random.default_rng([base, 2]), traffic["answer"], n)
    answers = np.minimum(answers, max_len - prompts)

    rng = np.random.default_rng([int(seed), 1])
    shared = traffic.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = rng.integers(0, vocab, (shared["groups"],
                                           shared["length"]))
    out = []
    for i in range(n):
        ids = rng.integers(0, vocab, int(prompts[i])).astype(np.int32)
        if shared and rng.uniform() < shared["share"]:
            k = min(shared["length"], ids.size - 1)
            ids[:k] = prefixes[rng.integers(0, shared["groups"])][:k]
        out.append({"due": float(due[i]), "prompt": ids,
                    "max_new": int(answers[i])})
    return out
