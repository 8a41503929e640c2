"""From a profiler trace (``.xplane.pb``) to the numbers a traced run prints.

The reduction lives with the benchmark so that every PR computes the same
number the same way. It reads the file with ``jax.profiler.ProfileData`` and
nothing else.

What a TPU trace holds (looked at by hand on a v5e, jax 0.9.0): one plane a
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event for every
HLO operation the chip ran (a ``while`` encloses the operations of its
body) and whose line ``XLA Modules`` has one event for every program
launched; and one plane ``/host:CPU`` with a line a thread, on which every
``jax.profiler.TraceAnnotation`` is an event. All planes share one clock.

Definitions:

- the *window* is the benchmark's own annotation ``bench/window``; events
  are clipped to it;
- a chip is *busy* in the union of its ``XLA Ops`` intervals, *idle* in
  the rest of the window; ``busy_s`` is the mean over the chips that ran
  anything;
- an operation's time is its *self* time: its interval less the
  operations nested in it, so a ``while`` is not counted twice;
- an idle gap is labelled by the innermost of the *label* annotations
  (those whose names the caller lists, by prefix) open on the window's
  thread; where several follow each other inside one gap it is split
  between them, and outside all of them it is ``unattributed``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench/window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
UNATTRIBUTED = "unattributed"

Interval = Tuple[float, float, str]          # start_ns, end_ns, name


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]              # ns on the trace's clock
    ops: Dict[str, List[Interval]]           # device plane -> operations
    launches: Dict[str, List[Interval]]      # device plane -> programs
    annotations: List[Interval]              # the window's thread

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a ``start_trace(trace_dir)`` session wrote."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def _events(line) -> List[Interval]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _clip(events: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return sorted((max(s, lo), min(e, hi), n) for s, e, n in events
                  if e > lo and s < hi)


def read(path: str) -> Trace:
    """Parse one ``.xplane.pb``. Raises if it holds no ``bench/window``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window, annotations = None, []
    ops: Dict[str, List[Interval]] = {}
    launches: Dict[str, List[Interval]] = {}
    for plane in data.planes:
        if plane.name.startswith(HOST_PLANE) and window is None:
            for line in plane.lines:
                events = _events(line)
                found = [ev for ev in events if ev[2] == WINDOW]
                if found:
                    window = (found[0][0], found[0][1])
                    annotations = events
                    break
        elif plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    launches[plane.name] = _events(line)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    lo, hi = window
    return Trace(
        window,
        {p: _clip(ev, lo, hi) for p, ev in ops.items()},
        {p: _clip(ev, lo, hi) for p, ev in launches.items()},
        _clip([a for a in annotations if a[2] != WINDOW], lo, hi))


def merged(events: Sequence[Interval]) -> List[Tuple[float, float]]:
    """Union of intervals (sorted by start), as disjoint (start, end)."""
    out: List[Tuple[float, float]] = []
    for s, e, _ in events:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, mean over the chips used."""
    per_chip = [sum(e - s for s, e in merged(ev)) * 1e-9
                for ev in trace.ops.values() if ev]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def launches(trace: Trace) -> int:
    """Programs launched in the window, on the busiest chip."""
    return max((len(ev) for ev in trace.launches.values()), default=0)


def self_times(events: Sequence[Interval]) -> Dict[str, float]:
    """name -> seconds of self time over one line's (nested) events."""
    total: Dict[str, float] = {}
    stack: List[list] = []               # [end, name, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            total[name] = total.get(name, 0.0) + self_ns * 1e-9

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return total


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """[[operation, seconds], ...]: the ``n`` with most self time, mean
    over the chips used."""
    chips = [ev for ev in trace.ops.values() if ev]
    total: Dict[str, float] = {}
    for ev in chips:
        for name, s in self_times(ev).items():
            total[name] = total.get(name, 0.0) + s / len(chips)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _innermost(annotations: Sequence[Interval], labels: Sequence[str],
               lo: float, hi: float) -> List[Interval]:
    """The window cut into pieces, each named by the innermost label
    annotation open there."""
    keep = sorted((a for a in annotations
                   if any(a[2].startswith(p) for p in labels)),
                  key=lambda a: (a[0], -a[1]))
    pieces: List[Interval] = []
    stack: List[Tuple[float, str]] = []  # (end, name)
    at = lo

    def emit(upto: float) -> None:
        nonlocal at
        if upto > at:
            pieces.append((at, upto, stack[-1][1] if stack else UNATTRIBUTED))
            at = upto

    for s, e, name in keep:
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return pieces


def idle_gaps(trace: Trace, labels: Sequence[str], n: int = 10) -> List[list]:
    """[[label, seconds], ...]: idle time of the busiest chip by what the
    host was doing, the ``n`` largest."""
    lo, hi = trace.window
    chips = [ev for ev in trace.ops.values() if ev]
    if not chips:
        return [[UNATTRIBUTED, trace.window_s]]
    busy = merged(max(chips, key=lambda ev: sum(e - s for s, e in merged(ev))))
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    pieces = _innermost(trace.annotations, labels, lo, hi)
    total: Dict[str, float] = {}
    i = 0
    for gs, ge in gaps:
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < ge:
            ps, pe, name = pieces[j]
            total[name] = total.get(name, 0.0) + (min(pe, ge) - max(ps, gs)) * 1e-9
            j += 1
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
