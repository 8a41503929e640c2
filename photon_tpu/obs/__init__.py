"""Unified telemetry: metrics registry, trace spans, memory watermarks,
device-resident solver telemetry, and the RunReport manifest.

The Spark-UI + ``Timed``/``OptimizationStatesTracker`` replacement
(reference: Photon ML debugs hundred-billion-coefficient GAME fits
through Spark's stage view; Snap ML's per-level pipeline accounting,
arXiv:1803.06333, is the design north star). One import surface::

    from photon_tpu import obs

    obs.configure(enabled=True)           # or PHOTON_TPU_TELEMETRY=1
    with obs.span("fit", configs=3):      # nested; Perfetto-exportable
        obs.metrics.counter("fits").inc()
    obs.write_trace("out/trace.json")     # chrome://tracing / Perfetto
    obs.write_run_report("out/runreport.json", driver="game-train")

Contracts:

  * **zero-overhead-when-disabled** — with telemetry off, ``span`` is two
    attribute writes, ``annotate`` returns a shared null context, memory
    sampling and solver recording return immediately; nothing is ever
    staged into jitted code either way (device series ride as ordinary
    solver outputs; ``scripts/check_no_host_sync.py`` enforces this).
  * **no collectives in hot paths** — multi-process aggregation happens
    once, at report time (obs/aggregate.py).
"""

from photon_tpu.obs._config import ENV_FLAG, configure, enabled
from photon_tpu.obs import memory
from photon_tpu.obs import solver as _solver_mod
from photon_tpu.obs.metrics import (
    MetricsRegistry,
    merge_snapshots,
    registry as metrics,
)
from photon_tpu.obs.spans import (
    annotate,
    chrome_trace_events,
    span,
    write_trace,
)

record_solver = _solver_mod.record
drain_solver_telemetry = _solver_mod.drain


def build_run_report(driver, mesh=None, extra=None):
    from photon_tpu.obs import report
    return report.build_run_report(driver, mesh=mesh, extra=extra)


def write_run_report(path, driver, mesh=None, extra=None, aggregate=False):
    from photon_tpu.obs import report
    return report.write_run_report(path, driver, mesh=mesh, extra=extra,
                                   aggregate=aggregate)


def validate_run_report(rep):
    from photon_tpu.obs import report
    return report.validate_run_report(rep)


def reset() -> None:
    """Clear every telemetry buffer and the enabled-override (tests)."""
    import sys as _sys

    from photon_tpu.obs import _config, spans
    _config.reset()
    metrics.clear()
    spans.clear()
    memory.clear()
    _solver_mod.clear()
    # the compile account's counters went with the registry; its buffer of
    # programs goes too (the jax.monitoring listeners stay: once a process)
    mod = _sys.modules.get("photon_tpu.utils.compile_cache")
    if mod is not None:
        mod.clear_programs()
    # windowed series + SLO verdicts: lazy (sys.modules) so offline
    # drivers that never touched them pay nothing here either
    for name in ("photon_tpu.obs.timeseries", "photon_tpu.obs.slo"):
        mod = _sys.modules.get(name)
        if mod is not None:
            mod.clear()


__all__ = [
    "ENV_FLAG", "configure", "enabled", "reset",
    "MetricsRegistry", "metrics", "merge_snapshots",
    "span", "annotate", "write_trace", "chrome_trace_events",
    "record_solver", "drain_solver_telemetry",
    "build_run_report", "write_run_report", "validate_run_report",
    "memory",
]
