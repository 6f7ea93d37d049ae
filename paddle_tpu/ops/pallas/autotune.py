"""Tile-shape autotuner for the Pallas kernels.

Every tile size in ops/pallas/ used to be a hard-coded guess (flag
defaults, VMEM-budget heuristics). With the ``autotune`` flag on, the
first *eager* contact with a (kernel, shape-signature, chip) triple
sweeps a small candidate set of block shapes through the live kernel,
times each, and caches the winner in a JSON file (``autotune_cache``
flag); every later contact — eager or traced — is a cache hit that
reuses the measured winner without re-sweeping. Off (the default),
kernels keep today's static defaults and this module costs one flag
check.

Inside a jit trace there is nothing to time, so a cache miss under
tracing quietly returns the static defaults — sweeps happen eagerly
(first un-jitted call, or ``tools/autotune.py``).

The cache doubles as the cost model's measurement feed: entries record
the candidate's achieved time and, when the caller supplies it, the
kernel's flop count — :func:`measured_rate` turns those into an
achieved-flops/s figure per chip that ``autoplan/costmodel.py`` uses in
place of its analytic ``peak * MFU_ASSUMED`` constant (and
``calibration_report()`` labels which source priced the plan).

Telemetry: ``autotune.sweeps{kernel}`` counts sweeps; ``autotune.cache
{event=hit|miss|corrupt}`` counts lookups and unreadable cache files.
Corrupt caches are tolerated — logged, counted, and rebuilt from
scratch, never raised into a training step.
"""

import json
import logging
import os

from paddle_tpu.observability import metrics as _metrics

logger = logging.getLogger("paddle_tpu.autotune")

_CACHE = None       # process-wide cache, keyed to the flag's path
_TIMER = None       # injectable timer (tests: set_timer(fake))

def signature(**dims):
    """Stable shape-signature string: ``signature(b=2, tq=128)`` ->
    ``"b2,tq128"``. Keys sort, so call sites need not agree on order."""
    return ",".join(f"{k}{v}" for k, v in sorted(dims.items()))


def chip_key(devices=None):
    """The chip the current backend runs on (``"v5e"``, ``"cpu"``, ...)
    — autoplan/topology.chip_name's answer, the same one its detect()
    keys on, so cache entries and topology presets agree on what a
    "chip" is."""
    import jax
    from paddle_tpu.parallel.autoplan.topology import chip_name
    return chip_name((list(devices) if devices is not None
                      else jax.devices())[0])


def cache_key(kernel, sig):
    return f"{kernel}|{sig}|{chip_key()}"


class AutotuneCache:
    """JSON-backed winner cache. File format (``version`` 1)::

        {"version": 1,
         "entries": {"<kernel>|<sig>|<chip>": {
             "blocks": {"block_q": 256, ...},   # the winning tile sizes
             "time_s": 1.3e-4,                  # its measured best-of time
             "flops": 2.1e9,                    # optional, for rate feeds
             "kernel": "...", "sig": "...", "chip": "...",
             "swept": [{"blocks": {...}, "time_s": ...}, ...]}}}

    Unreadable or wrong-shaped files count ``autotune.cache{event=
    corrupt}`` and are rebuilt — a stale cache must never take down a
    run."""

    def __init__(self, path):
        self.path = path
        self.entries = {}
        self._loaded = False

    def load(self):
        if self._loaded:
            return self
        self._loaded = True
        try:
            with open(self.path) as f:
                data = json.load(f)
            entries = data.get("entries") if isinstance(data, dict) else None
            if not isinstance(entries, dict):
                raise ValueError("missing 'entries' table")
            self.entries = entries
        except FileNotFoundError:
            pass
        except Exception as e:
            _metrics.counter("autotune.cache").inc(event="corrupt")
            logger.warning("autotune cache %s unreadable (%s); starting "
                           "fresh", self.path, e)
        return self

    def get(self, key):
        return self.load().entries.get(key)

    def put(self, key, record):
        self.load().entries[key] = record
        try:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"version": 1, "entries": self.entries}, f,
                          indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError as e:  # read-only fs: keep the in-memory winner
            logger.warning("autotune cache %s not writable (%s)",
                           self.path, e)

    def clear(self):
        self.entries = {}
        self._loaded = True
        try:
            os.remove(self.path)
        except OSError:
            pass


def cache(path=None):
    """The process cache for ``path`` (default: the ``autotune_cache``
    flag). Re-resolved per call so tests repointing the flag get a fresh
    cache."""
    global _CACHE
    if path is None:
        from paddle_tpu.core.flags import get_flag
        path = get_flag("autotune_cache")
    if _CACHE is None or _CACHE.path != path:
        _CACHE = AutotuneCache(path)
    return _CACHE


def set_timer(timer):
    """Override the candidate timer (tests inject a deterministic fake:
    ``timer(thunk) -> seconds``). None restores wall-clock timing."""
    global _TIMER
    _TIMER = timer


def default_timer(thunk, reps=3):
    """Best-of-``reps`` wall time of ``thunk``, compile excluded (one
    warmup call) and dispatch settled (block_until_ready)."""
    import time

    import jax
    jax.block_until_ready(thunk())          # warmup: compile + first run
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        best = min(best, time.perf_counter() - t0)
    return best


def _is_traced(args):
    import jax
    return any(isinstance(a, jax.core.Tracer) for a in args)


def tuned_blocks(kernel, sig, defaults, candidates=None, runner=None,
                 flops=None, args=()):
    """Resolve tile sizes for one (kernel, shape-signature, chip) triple.

    The one call a kernel dispatcher makes: with the ``autotune`` flag
    off this is ``dict(defaults)``; on, a cached winner is a hit (no
    sweep — counter-verified by tests); a miss with concrete ``args``
    and a ``runner`` sweeps now; a miss under tracing (or with no
    runner) falls back to the static defaults.

    ``runner(**blocks)`` must execute the kernel with those tile sizes;
    ``candidates`` is a list of partial block dicts (or a thunk
    returning one — deferred so the flag-off path never builds it);
    ``flops`` (optional) records the kernel's flop count so the cost
    model can derive an achieved-flops/s rate from the winner.
    """
    from paddle_tpu.core.flags import get_flag
    if not get_flag("autotune"):
        return dict(defaults)
    rec = cache().get(cache_key(kernel, sig))
    if rec is not None and isinstance(rec.get("blocks"), dict):
        _metrics.counter("autotune.cache").inc(event="hit")
        out = dict(defaults)
        out.update({k: v for k, v in rec["blocks"].items() if k in defaults})
        return out
    _metrics.counter("autotune.cache").inc(event="miss")
    if runner is None or _is_traced(args):
        return dict(defaults)
    return sweep(kernel, sig, defaults, candidates, runner,
                 flops=flops)["blocks"]


def sweep(kernel, sig, defaults, candidates, runner, flops=None):
    """Time every candidate through ``runner`` and cache the winner.
    Returns the full cache record (winner + the ranked ``swept`` list —
    what ``tools/autotune.py`` prints). The defaults are always swept
    too, so the winner can only match or beat them; a candidate that
    raises (illegal tile) is skipped, and if every candidate fails the
    defaults win with no measured time."""
    timer = _TIMER or default_timer
    cands = candidates() if callable(candidates) else list(candidates or [])
    seen, uniq = set(), []
    for c in [dict(defaults)] + [dict(defaults, **c) for c in cands]:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            uniq.append(c)
    _metrics.counter("autotune.sweeps").inc(kernel=kernel)
    results = []
    for c in uniq:
        try:
            t = float(timer(lambda c=c: runner(**c)))
        except Exception as e:
            logger.debug("autotune %s: candidate %s failed (%s)",
                         kernel, c, e)
            continue
        results.append({"blocks": c, "time_s": t})
    results.sort(key=lambda r: r["time_s"])
    if results:
        best, time_s = results[0]["blocks"], results[0]["time_s"]
    else:
        best, time_s = dict(defaults), None
    record = {"blocks": best, "time_s": time_s, "kernel": kernel,
              "sig": sig, "chip": chip_key(), "swept": results}
    if flops:
        record["flops"] = float(flops)
    cache().put(cache_key(kernel, sig), record)
    return record


# ----------------------------------------------------- cost-model feed

def measured_rates(path=None):
    """{chip: [achieved flops/s, ...]} over cache entries that carry both
    a measured time and a flop count."""
    out = {}
    for rec in cache(path).load().entries.values():
        t, f = rec.get("time_s"), rec.get("flops")
        if t and f and t > 0:
            out.setdefault(rec.get("chip", "cpu"), []).append(f / t)
    return out


def measured_rate(chip, path=None):
    """(harmonic-mean achieved flops/s, entry count) for ``chip``, or
    None with no measurements. Harmonic mean: rates combine over the
    time the kernels actually spend."""
    rates = measured_rates(path).get(chip)
    if not rates:
        return None
    return len(rates) / sum(1.0 / r for r in rates), len(rates)
