"""Observability — the run-scoped telemetry subsystem.

Reference-framework ancestry (what each piece re-architects):

  metrics.py    counters/gauges/histograms in ONE process registry — the
                successor of the reference's scattered monitor state
                (pserver HeartBeatMonitor tallies, profiler totals,
                ad-hoc VLOG counters); every degraded path in this
                framework (core/retry.py attempts, ops/pallas fallbacks,
                io/checkpoint.py torn-commit skips and mirror
                degradations, parallel/heartbeat.py missed beats,
                static/trainer.py preemptions + ingest stalls) now
                increments a named metric here.
  runlog.py     JSONL step-record sink with rotation — the durable,
                machine-readable run artifact the reference never had
                (DeviceWorker VLOG lines were the closest thing).
  spans.py      nestable span() scopes — platform/profiler.h:81
                RecordEvent, feeding the metrics registry (and from it
                the sorted text table, profiler.h:166 EnableProfiler),
                jax.profiler.TraceAnnotation (the chrome-trace timeline
                role of tools/timeline.py) and, while a profiler
                session is on, the bounded in-memory span store
                (start, end, parent, request id, counts).
  perf.py       peak-FLOPs table + XLA cost-analysis + device memory
                stats (step records, the autoplan calibration and
                tools/run_report.py share one MFU arithmetic).
  telemetry.py  TelemetryConfig/StepTelemetry — opt-in per-step records
                (wall time, tokens/s, MFU, trailing-fetch loss, HBM
                peaks) emitted from static/trainer.py with no device
                sync on the hot path.
  catalog.py    the one table of every metric name/type/labels/help;
                exporter HELP lines come from it and a tier-1 lint
                fails on call sites naming uncataloged metrics.
  exporter.py   Prometheus text exposition of the whole registry +
                a stdlib /metrics + /healthz HTTP server (flag
                metrics_port; start_metrics_server()).
  watchdog.py   rolling-window anomaly monitor (slow-step, ingest
                stall, steady-state retrace, goodput collapse) latching
                watchdog.anomalies{kind} + RunLog events; fed by the
                Trainer loop and the serving engine.
  trace.py      fleet-wide distributed tracing — durable trace contexts
                minted at FleetRouter.submit() and carried across
                dispatch/failover hops, per-process clock anchors, and
                the skew-corrected cross-replica timeline merge behind
                tools/run_report.py --fleet-trace.
  flight.py     anomaly-triggered flight recorder — bounded ring of
                recent trace events + dump_bundle() evidence bundles
                (metrics, ring, RunLog tails, config, optional XPlane)
                fired from the watchdog action hook.

tools/run_report.py joins a RunLog with an optional XPlane trace dir
into the human-readable run report (the EnableProfiler/DisableProfiler
report + timeline.py join, in one CLI).

`metrics` and `runlog` are import-light (stdlib only) so early modules
(core/retry.py) can use them without cycles; the jax-importing members
(span, TelemetryConfig, ...) load lazily on first attribute access.
"""

from paddle_tpu.observability import metrics, runlog
from paddle_tpu.observability.metrics import (Counter, Gauge, Histogram,
                                              MetricsRegistry, counter,
                                              gauge, histogram, registry,
                                              reset_all, snapshot)
from paddle_tpu.observability.runlog import (RunLog, read_records,
                                             tail_records)

# lazily-resolved members -> defining submodule (PEP 562): these pull in
# jax/profiler, which early importers of the metrics registry must not
_LAZY = {
    "span": "spans", "span_summary": "spans",
    "span_report": "spans", "reset_spans": "spans",
    "spans": None, "telemetry": None, "perf": None,
    "catalog": None, "exporter": None, "watchdog": None,
    "trace": None, "flight": None,
    "TraceContext": "trace", "merge_fleet_trace": "trace",
    "write_anchor": "trace",
    "FlightRecorder": "flight", "dump_bundle": "flight",
    "last_bundle": "flight",
    "TelemetryConfig": "telemetry", "StepTelemetry": "telemetry",
    "default_tokens": "telemetry",
    "peak_flops": "perf", "cost_flops": "perf", "mfu": "perf",
    "device_memory_stats": "perf",
    "MetricsServer": "exporter", "render_prometheus": "exporter",
    "start_metrics_server": "exporter",
    "Watchdog": "watchdog", "WatchdogConfig": "watchdog",
    "maybe_watchdog": "watchdog",
}


def __getattr__(name):
    import importlib
    target = _LAZY.get(name, KeyError)
    if target is KeyError:
        raise AttributeError(
            f"module 'paddle_tpu.observability' has no attribute {name!r}")
    if target is None:      # the submodule itself
        return importlib.import_module(f"paddle_tpu.observability.{name}")
    mod = importlib.import_module(f"paddle_tpu.observability.{target}")
    val = getattr(mod, name)
    globals()[name] = val   # cache: subsequent accesses skip __getattr__
    return val

