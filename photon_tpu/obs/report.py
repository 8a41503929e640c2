"""RunReport: one machine-readable JSON manifest per driver run.

Written at the end of ``cli/train.py`` / ``cli/score.py``: phase spans,
the metrics-registry snapshot, drained solver trajectories (per-iteration
loss/||g||/step series and per-entity RE outcomes), mesh/device topology,
and host/device memory watermarks sampled per phase. The schema is versioned so
later perf/robustness PRs can extend it without breaking parsers.

Multi-process: :func:`write_run_report` with ``aggregate=True`` gathers
every process's metrics/memory/solver sections to process 0 (two
collectives at report time — obs/aggregate.py) and only process 0
writes; other processes return ``None``.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

SCHEMA = "photon_tpu.runreport.v1"


def _topology(mesh=None) -> Dict[str, Any]:
    """Device/mesh topology; degrades to {} when jax isn't loaded."""
    if sys.modules.get("jax") is None:
        return {}
    try:
        from photon_tpu.parallel.mesh import mesh_topology
        return mesh_topology(mesh)
    except Exception:  # backend not initialized — report stays valid
        return {}


def _phases() -> List[Dict[str, Any]]:
    from photon_tpu.obs import spans
    out = []
    for r in spans.records():
        p = {
            "name": r["name"],
            "start_unix": r["start_unix"],
            "end_unix": r["end_unix"],
            "duration_s": r["dur_us"] / 1e6,
            "parent": r.get("parent"),
            "depth": r.get("depth", 0),
            "tid": r.get("tid"),
        }
        if "args" in r:
            p["args"] = r["args"]
        if r.get("error"):
            p["error"] = True
        out.append(p)
    return out


def build_run_report(driver: str,
                     mesh=None,
                     extra: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """Assemble this process's report dict. Draining solver telemetry and
    sampling memory happen here — this IS the phase boundary."""
    from photon_tpu.obs import aggregate, memory, solver
    from photon_tpu.obs.metrics import registry
    from photon_tpu.resilience import failures
    from photon_tpu.utils import timing

    memory.record_phase("run_report")  # final watermark sample
    report: Dict[str, Any] = {
        "schema": SCHEMA,
        "driver": driver,
        "created_unix": time.time(),
        "argv": list(sys.argv),
        "process": aggregate.process_info(),
        "topology": _topology(mesh),
        "phases": _phases(),
        "timings": [[label, secs] for label, secs in timing.timing_records()],
        "metrics": registry.snapshot(),
        "solver": solver.drain(),
        "memory": memory.watermarks(),
        "failures": failures.snapshot(),
    }
    serving = _serving_section()
    if serving is not None:
        report["serving"] = serving
    cd = _cd_section()
    if cd is not None:
        report["cd"] = cd
    nearline = _nearline_section()
    if nearline is not None:
        report["nearline"] = nearline
    sweep = _sweep_section()
    if sweep is not None:
        report["sweep"] = sweep
    sdca = _sdca_section()
    if sdca is not None:
        report["sdca"] = sdca
    re_plan = _re_plan_section()
    if re_plan is not None:
        report["re_plan"] = re_plan
    timeline = _timeline_section()
    if timeline is not None:
        report["timeline"] = timeline
    slo = _slo_section()
    if slo is not None:
        report["slo"] = slo
    compiled = _compile_section()
    if compiled is not None:
        report["compile"] = compiled
    if extra:
        report["extra"] = extra
    return report


def _compile_section() -> Optional[Dict[str, Any]]:
    """The job's compile account (utils/compile_cache.py): seconds and
    programs by stage (trace / lower / cache_load / backend) and phase
    (``during``), the persistent cache's outcomes and the ten slowest
    programs by name. Same ``sys.modules`` pattern as
    :func:`_serving_section`: a process that never built a program has no
    section."""
    mod = sys.modules.get("photon_tpu.utils.compile_cache")
    if mod is None:
        return None
    try:
        section = mod.report_section()
        return section if section["programs"] else None
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def _serving_section() -> Optional[Dict[str, Any]]:
    """The active serving engine's ``stats()``, when this process is a
    serving process. Deliberately read via ``sys.modules`` — an offline
    driver that never imported photon_tpu.serving pays nothing and its
    report is unchanged."""
    mod = sys.modules.get("photon_tpu.serving")
    if mod is None:
        return None
    try:
        return mod.serving_report_section()
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def _cd_section() -> Optional[Dict[str, Any]]:
    """Parallel coordinate-descent statistics (group/staleness/fallback
    accounting), when this process ran a parallel sweep. Same
    ``sys.modules`` pattern as :func:`_serving_section` — sequential-only
    and non-training processes pay nothing."""
    mod = sys.modules.get("photon_tpu.game.parallel_cd")
    if mod is None:
        return None
    try:
        return mod.report_section()
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def _nearline_section() -> Optional[Dict[str, Any]]:
    """The active nearline pipeline's summary (rounds, watermark,
    publish/rollback totals, reader stats), when this process ran one.
    Same ``sys.modules`` pattern as :func:`_serving_section`."""
    mod = sys.modules.get("photon_tpu.nearline.pipeline")
    if mod is None:
        return None
    try:
        return mod.report_section()
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def _sweep_section() -> Optional[Dict[str, Any]]:
    """Lane-batched sweep/tuner accounting (batched solves, per-lane
    outcomes, tuner round summary), when this process ran one. Same
    ``sys.modules`` pattern as :func:`_serving_section` — runs that never
    sweep pay nothing."""
    mod = sys.modules.get("photon_tpu.optim.batched")
    if mod is None:
        return None
    try:
        section = mod.report_section()
        # an imported-but-idle batched module stays out of the report
        return section if section.get("runs") else None
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def _re_plan_section() -> Optional[Dict[str, Any]]:
    """Random-effect sweep HBM planning (plans emitted, degraded /
    over-budget bucket counts, the last plan) — a refused or degraded
    sweep shape is DATA in the report, not a crash. Same ``sys.modules``
    pattern as :func:`_serving_section`; the section itself returns None
    while no sweep has been planned."""
    mod = sys.modules.get("photon_tpu.parallel.memory")
    if mod is None:
        return None
    try:
        return mod.report_section()
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def _timeline_section() -> Optional[Dict[str, Any]]:
    """Windowed time-series telemetry (obs/timeseries.py), when this
    process recorded any. Same ``sys.modules`` pattern as
    :func:`_serving_section` — offline drivers that never touch the
    windowed registry pay nothing; the section itself returns None
    while it is empty."""
    mod = sys.modules.get("photon_tpu.obs.timeseries")
    if mod is None:
        return None
    try:
        return mod.report_section()
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def _slo_section() -> Optional[Dict[str, Any]]:
    """SLO verdicts (obs/slo.py) recorded by any evaluation this run.
    Same ``sys.modules`` pattern as :func:`_serving_section`; the
    section itself returns None while nothing was evaluated."""
    mod = sys.modules.get("photon_tpu.obs.slo")
    if mod is None:
        return None
    try:
        return mod.report_section()
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def _sdca_section() -> Optional[Dict[str, Any]]:
    """Stochastic dual (SDCA) solve accounting — runs/epochs/staleness
    fallbacks and the last run's gap outcome — when this process ran one.
    Same ``sys.modules`` pattern as :func:`_serving_section`; the section
    itself returns None while no solve has run."""
    mod = sys.modules.get("photon_tpu.optim.sdca")
    if mod is None:
        return None
    try:
        return mod.report_section()
    except Exception:  # noqa: BLE001 — reporting must not kill a run
        return None


def write_run_report(path: str,
                     driver: str,
                     mesh=None,
                     extra: Optional[Dict[str, Any]] = None,
                     aggregate: bool = False) -> Optional[Dict[str, Any]]:
    """Build + write the report; returns the written dict.

    With ``aggregate=True`` on a multi-process run, every process must
    call this (the gather is collective); only process 0 writes and
    returns the report — it gains a ``processes`` section with each
    process's metrics/memory/solver and cluster-merged ``metrics``
    under ``metrics_aggregated``.
    """
    from photon_tpu.obs import aggregate as agg
    from photon_tpu.obs.metrics import merge_snapshots

    report = build_run_report(driver, mesh=mesh, extra=extra)
    if aggregate and report["process"]["count"] > 1:
        local = {
            "process": report["process"],
            "metrics": report["metrics"],
            "memory": report["memory"],
            "solver": report["solver"],
            "num_phases": len(report["phases"]),
        }
        gathered = agg.gather_payloads(local)
        if gathered is None:  # non-zero process: report written by proc 0
            return None
        report["processes"] = gathered
        report["metrics_aggregated"] = merge_snapshots(
            p["metrics"] for p in gathered)

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1, default=_json_fallback)
        f.write("\n")
    return report


def _json_fallback(obj):
    """Numpy scalars/arrays sneak into extras; make them JSON-safe rather
    than killing the report at the end of a long run."""
    try:
        import numpy as np
        if isinstance(obj, np.ndarray):
            return obj.tolist()
        if isinstance(obj, np.generic):
            return obj.item()
    except ImportError:  # pragma: no cover
        pass
    return str(obj)


def validate_run_report(report: Dict[str, Any]) -> List[str]:
    """Structural schema check; returns a list of problems ([] = valid).
    Used by tests."""
    errors: List[str] = []
    if report.get("schema") != SCHEMA:
        errors.append(f"schema is {report.get('schema')!r}, want {SCHEMA!r}")
    if not isinstance(report.get("driver"), str) or not report.get("driver"):
        errors.append("driver must be a non-empty string")
    if not isinstance(report.get("created_unix"), (int, float)):
        errors.append("created_unix must be a number")
    phases = report.get("phases")
    if not isinstance(phases, list):
        errors.append("phases must be a list")
    else:
        for i, p in enumerate(phases):
            for k in ("name", "start_unix", "end_unix", "duration_s"):
                if k not in p:
                    errors.append(f"phases[{i}] missing {k!r}")
            if ("start_unix" in p and "end_unix" in p
                    and p["start_unix"] > p["end_unix"] + 1e-9):
                errors.append(f"phases[{i}] ({p.get('name')}): start > end")
            if p.get("duration_s", 0) < 0:
                errors.append(f"phases[{i}]: negative duration")
    metrics = report.get("metrics")
    if not isinstance(metrics, dict):
        errors.append("metrics must be a dict")
    else:
        for section in ("counters", "gauges", "histograms"):
            if not isinstance(metrics.get(section), dict):
                errors.append(f"metrics.{section} must be a dict")
    solver = report.get("solver")
    if not isinstance(solver, dict):
        errors.append("solver must be a dict")
    else:
        for section in ("trajectories", "random_effects"):
            if not isinstance(solver.get(section), list):
                errors.append(f"solver.{section} must be a list")
    if not isinstance(report.get("memory"), dict):
        errors.append("memory must be a dict")
    if not isinstance(report.get("failures"), list):
        errors.append("failures must be a list")
    proc = report.get("process")
    if (not isinstance(proc, dict) or "index" not in proc
            or "count" not in proc):
        errors.append("process must be {'index', 'count'}")
    if "compile" in report:  # optional: only processes that built a program
        compiled = report["compile"]
        if not isinstance(compiled, dict):
            errors.append("compile must be a dict")
        else:
            for k in ("seconds", "programs", "cache", "slowest"):
                if k not in compiled:
                    errors.append(f"compile missing {k!r}")
            for i, r in enumerate(compiled.get("slowest", [])):
                for k in ("fun", "stage", "seconds", "start_unix", "during"):
                    if k not in r:
                        errors.append(f"compile.slowest[{i}] missing {k!r}")
    if "serving" in report:  # optional: only serving processes emit it
        serving = report["serving"]
        if not isinstance(serving, dict):
            errors.append("serving must be a dict")
        else:
            for k in ("buckets", "compile_counts", "counters",
                      "latency_seconds"):
                if k not in serving:
                    errors.append(f"serving missing {k!r}")
            if "swap" in serving:  # optional: engines with swap support
                swap = serving["swap"]
                if not isinstance(swap, dict):
                    errors.append("serving.swap must be a dict")
                else:
                    for k in ("version", "history"):
                        if k not in swap:
                            errors.append(f"serving.swap missing {k!r}")
                    if not isinstance(swap.get("history", []), list):
                        errors.append("serving.swap history must be a list")
    if "sweep" in report:  # optional: only lane-batched sweep processes
        sweep = report["sweep"]
        if not isinstance(sweep, dict):
            errors.append("sweep must be a dict")
        else:
            for k in ("runs", "lanes_total", "lane_records", "tuner"):
                if k not in sweep:
                    errors.append(f"sweep missing {k!r}")
            if not isinstance(sweep.get("lane_records", []), list):
                errors.append("sweep.lane_records must be a list")
    if "sdca" in report:  # optional: only stochastic-dual training runs
        sdca = report["sdca"]
        if not isinstance(sdca, dict):
            errors.append("sdca must be a dict")
        else:
            for k in ("runs", "epochs", "fallbacks", "converged"):
                if k not in sdca:
                    errors.append(f"sdca missing {k!r}")
    if "re_plan" in report:  # optional: only RE-sweep planning processes
        re_plan = report["re_plan"]
        if not isinstance(re_plan, dict):
            errors.append("re_plan must be a dict")
        else:
            for k in ("plans", "buckets_degraded", "buckets_over_budget",
                      "last_plan"):
                if k not in re_plan:
                    errors.append(f"re_plan missing {k!r}")
    if "timeline" in report:  # optional: only windowed-telemetry runs
        timeline = report["timeline"]
        if not isinstance(timeline, dict):
            errors.append("timeline must be a dict")
        else:
            if not isinstance(timeline.get("interval_s"), (int, float)) \
                    or timeline.get("interval_s", 0) <= 0:
                errors.append("timeline.interval_s must be positive")
            series_map = timeline.get("series")
            if not isinstance(series_map, dict):
                errors.append("timeline.series must be a dict")
            else:
                for key, s in series_map.items():
                    if not isinstance(s, dict):
                        errors.append(f"timeline.series[{key!r}] not a dict")
                        continue
                    if s.get("kind") not in ("counter", "gauge", "quantile"):
                        errors.append(
                            f"timeline.series[{key!r}] bad kind "
                            f"{s.get('kind')!r}")
                    windows = s.get("windows")
                    if not isinstance(windows, list):
                        errors.append(
                            f"timeline.series[{key!r}].windows not a list")
                        continue
                    idxs = [w.get("idx") for w in windows
                            if isinstance(w, dict)]
                    if len(idxs) != len(windows) or idxs != sorted(idxs):
                        errors.append(
                            f"timeline.series[{key!r}] windows must carry "
                            f"sorted idx fields")
    if "slo" in report:  # optional: only runs that evaluated SLO specs
        slo = report["slo"]
        if not isinstance(slo, dict):
            errors.append("slo must be a dict")
        else:
            if slo.get("status") not in ("PASS", "WARN", "BREACH"):
                errors.append(f"slo.status invalid: {slo.get('status')!r}")
            verdicts = slo.get("verdicts")
            if not isinstance(verdicts, list):
                errors.append("slo.verdicts must be a list")
            else:
                for i, v in enumerate(verdicts):
                    if not isinstance(v, dict):
                        errors.append(f"slo.verdicts[{i}] not a dict")
                        continue
                    for k in ("rule_id", "kind", "status",
                              "offending_windows"):
                        if k not in v:
                            errors.append(f"slo.verdicts[{i}] missing {k!r}")
                    if v.get("status") not in ("PASS", "WARN", "BREACH"):
                        errors.append(
                            f"slo.verdicts[{i}] bad status "
                            f"{v.get('status')!r}")
                    if not isinstance(v.get("offending_windows", []), list):
                        errors.append(
                            f"slo.verdicts[{i}].offending_windows "
                            f"must be a list")
    if "cd" in report:  # optional: only parallel-CD training processes
        cd = report["cd"]
        if not isinstance(cd, dict) or not isinstance(
                cd.get("parallel"), dict):
            errors.append("cd must be {'parallel': {...}}")
        else:
            par = cd["parallel"]
            for k in ("runs", "groups", "groups_run", "members_solved",
                      "stale_regressions", "fallbacks", "group_records"):
                if k not in par:
                    errors.append(f"cd.parallel missing {k!r}")
            if not isinstance(par.get("group_records", []), list):
                errors.append("cd.parallel group_records must be a list")
    return errors
