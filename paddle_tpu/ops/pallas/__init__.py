"""Hand-written Pallas TPU kernels.

The counterpart of the reference's hand-written CUDA kernels
(/root/reference/paddle/fluid/operators/*.cu, operators/math/*.cu,
operators/jit/ x86 codegen): where XLA's automatic fusion isn't enough, we
drop to Pallas for explicit VMEM tiling and MXU scheduling.

Kernels run on a TPU (or, with ``pallas_interpret``, through the Pallas
interpreter); elsewhere each dispatcher hands back its pure-XLA twin
(what plain CPU tests run).
"""

import logging

import jax

from paddle_tpu.observability import metrics as _metrics

logger = logging.getLogger("paddle_tpu.pallas")
_fallback_logged = set()


def log_fallback(kernel, reason, level=logging.WARNING):
    """One-time notice when a Pallas fast path is refused, so a user
    benchmarking the "fused" configuration knows they are measuring the
    chunked XLA fallback. Callers include the *requested* configuration
    (shapes, layout, sharding) vs. what the kernel supports in `reason` —
    a silent drop under GSPMD is otherwise invisible.

    Every refusal (not just the first) also increments the
    `pallas.fallback{kernel=...}` counter, so a run's final telemetry
    snapshot names which kernels ran their XLA fallback — the log line
    is one-time, the counter is the record."""
    _metrics.counter("pallas.fallback").inc(kernel=kernel)
    key = (kernel, reason)
    if key not in _fallback_logged:
        _fallback_logged.add(key)
        logger.log(level, "%s: Pallas path refused (%s); "
                          "using chunked XLA fallback", kernel, reason)


def describe_sharding(**arrays):
    """Compact "name=shape@spec" string for fallback log lines. Concrete
    arrays report their NamedSharding spec; tracers (inside jit, where
    shardings are GSPMD-deferred) report '?'."""
    parts = []
    for name, a in arrays.items():
        try:
            spec = a.sharding.spec
        except Exception:
            spec = "?"
        parts.append(f"{name}={tuple(getattr(a, 'shape', ()))}@{spec}")
    return ", ".join(parts)


def on_tpu():
    """True when device 0 is a TPU. No guard: a backend that cannot be
    asked raises here rather than answering "no" and handing every
    caller its XLA twin."""
    return jax.devices()[0].platform == "tpu"
