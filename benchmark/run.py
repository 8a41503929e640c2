#!/usr/bin/env python3
"""One run of one cell: load, warm up, measure, check, print one line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``benchmark/workloads/<cell>.json``; it names a configuration
(``benchmark/configs/``, with its plain reference in ``benchmark/reference/``)
and a traffic kind (``benchmark/traffic/<kind>.py``), and lists the metrics it
reports, each a reader of its own under ``benchmark/end_to_end/`` or
``benchmark/layer_metrics/``. Nothing here names a cell, a configuration, a
kind or a metric: adding one is adding files (benchmark/README.md).

The last line of stdout is the result, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, with ``--trace 1``,
``breakdown``. Everything else goes on earlier lines or into
``benchmark/out/<cell>/``. ``--trace 0`` prints the cell's end-to-end
metrics and starts no profiler; ``--trace 1`` prints its per-layer metrics,
with the profiler on for the last ``trace_seconds`` of the window only.

Without an accelerator that ``benchmark/peaks.json`` knows, or with fewer
chips than the cell asks for, the run fails (exit 2, no result line); it
never falls back to a CPU. ``--rehearse`` is the exception made for
section 2 of the on-chip guide: CPU, the tiny sizes under ``rehearse`` in
the cell's files, and only counts in ``metrics``. The driver never passes it.
"""

import time

_PROCESS_START = time.perf_counter()

import argparse
import dataclasses
import importlib
import json
import os
import shutil
import sys
import traceback
from typing import Any, Callable, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def overlaid(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, dictionaries merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (overlaid(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


class CompileClock:
    """XLA's own account of compilation, read from ``jax.monitoring``:
    seconds in backend compiles (a persistent-cache hit costs only its
    retrieval), how many programs, and how many the cache served. Copied
    from chip_smoke.py."""

    def __enter__(self):
        import jax.monitoring as monitoring

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Context:
    """What a traffic kind is given."""

    cell: dict
    cfg: dict
    seed: int
    rehearse: bool
    out_dir: str
    say: Callable[[str], None] = say


@dataclasses.dataclass
class Run:
    """What a metric's reader is given."""

    cell: dict
    cfg: dict
    setup_s: float
    state: Any                     # what the kind's setup() returned
    window: dict                   # the kind's samples, profiler off
    traced: Optional[dict]         # the kind's samples under the profiler
    trace: Any                     # trace_reader.Trace, or None
    compile: Dict[str, float]
    memory_peak_bytes: Optional[int]
    peaks: Optional[dict]          # this device's row of peaks.json


def _traced(out_dir: str, body: Callable[[], dict]):
    import jax

    from benchmark import trace_reader

    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # or every Python call is an event
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reader.WINDOW):
            samples = body()
    finally:
        jax.profiler.stop_trace()
    path = trace_reader.find_xplane(trace_dir)
    say(f"trace: {path} ({os.path.getsize(path)} bytes)")
    return samples, trace_reader.read(path)


def _metric(package: str, name: str):
    return importlib.import_module(f"benchmark.{package}.{name}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU, tiny sizes, counts only; never a measurement")
    args = p.parse_args(argv)

    cell = load_json("workloads", f"{args.workload}.json")
    cfg = load_json("configs", f"{cell['config']}.json")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cell = overlaid(cell, cell.get("rehearse", {}))
        cfg = overlaid(cfg, cfg.get("rehearse", {}))

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peaks = load_json("peaks.json").get(device["kind"])
    if not args.rehearse and (device["platform"] == "cpu" or peaks is None
                              or len(devices) < cell["chips"]):
        print(f"benchmark/run.py: cell {args.workload!r} needs "
              f"{cell['chips']} chip(s) of a kind in benchmark/peaks.json; "
              f"jax.devices() is {devices}", file=sys.stderr)
        return 2
    say(f"cell {args.workload} seed {args.seed} on {device}")

    from photon_tpu import obs
    from photon_tpu.utils import compile_cache

    say(f"compile cache: {compile_cache.maybe_enable()}")
    out_dir = os.path.join(HERE, "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(cell, cfg, args.seed, args.rehearse, out_dir)
    kind = importlib.import_module(
        f"benchmark.traffic.{cell['traffic']['kind']}")

    with CompileClock() as clock:
        state = kind.setup(ctx)
        setup_s = time.perf_counter() - _PROCESS_START
        compiled = (clock.seconds, clock.programs, clock.cache_hits)
        say(f"set-up {setup_s:.2f}s: {clock.programs} programs, "
            f"{clock.cache_hits} from the persistent cache, "
            f"{clock.seconds:.2f}s in backend compiles")
        traced = trace = None
        if args.trace:
            # the program's spans become annotations in the trace only
            # with its telemetry on; an end-to-end run leaves it off
            obs.configure(enabled=True)
            tail = min(cell["trace_seconds"], args.seconds / 2)
            window = kind.measure(ctx, state, args.seconds - tail)
            traced, trace = _traced(
                out_dir, lambda: kind.measure(ctx, state, tail))
        else:
            window = kind.measure(ctx, state, args.seconds)
        window_compiles = clock.programs - compiled[1]
    correct, attempted, failed = kind.verify(
        ctx, state, [w for w in (window, traced) if w is not None])

    peak = [d.memory_stats().get("peak_bytes_in_use")
            for d in devices[:cell["chips"]] if d.memory_stats()]
    run = Run(cell, cfg, setup_s, state, window, traced, trace,
              {"compile_s": compiled[0], "programs": compiled[1],
               "cache_hits": compiled[2], "window_compiles": window_compiles},
              max(peak) if peak else None, peaks)

    package = "layer_metrics" if args.trace else "end_to_end"
    metrics = {}
    for name in cell["per_layer" if args.trace else "end_to_end"]:
        reader = _metric(package, name)
        if args.rehearse and reader.SOURCE != "program_counter":
            continue                     # a CPU run prints counts only
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    device["memory_peak_bytes"] = run.memory_peak_bytes
    result = {"correct": bool(correct and window_compiles == 0),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None and not args.rehearse:
        from benchmark import trace_reader

        device["busy_s"] = trace_reader.busy_s(trace)
        device["window_s"] = trace.window_s
        # names as XLA prints them, cut where a `while` lists its whole carry
        result["breakdown"] = {
            "device_ops": [[name[:200], s]
                           for name, s in trace_reader.top_ops(trace)],
            "idle_gaps": trace_reader.idle_gaps(trace, kind.GAP_LABELS)}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
