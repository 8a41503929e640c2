"""From a profiler trace (``.xplane.pb``) to device seconds by the program's
own names: which ``jax.named_scope`` each operation of the traced window ran
under.

``trace_reader.py`` reads the file with ``jax.profiler.ProfileData``, which
shows an event's name, start and duration and nothing of its *metadata*.
The metadata is where XLA keeps what is needed here: every ``XLA Ops`` event
points (``metadata_id``) at an ``XEventMetadata`` of its plane, whose stats
hold ``tf_op`` (JAX's name stack and primitive, e.g.
``jit(solve)/optim/lbfgs/loop/while/body/optim/lbfgs/linesearch/.../
agg/value_and_gradient/dot_general:``), ``bytes_accessed``, ``hlo_category``
and ``program_id``. So this file parses the protocol buffer itself, with
``google.protobuf`` and a description of the few ``XSpace`` fields it reads,
built in code (the generated ``xplane_pb2`` ships only inside TensorFlow,
which takes 15 s to import and must not be loaded into the process that
holds the chip). Fields it does not describe are skipped by the parser.

Definitions, the same as ``trace_reader``'s wherever they overlap:

- events are clipped to the window (``bench/window``); an operation counts
  its *self* time, its interval less the operations nested in it, so a
  ``while`` is not counted twice; seconds are the mean over the chips that
  ran anything, and sum to ``trace_reader.busy_s``;
- operations are joined to their metadata by ``metadata_id``, never by
  name: HLO names such as ``%copy`` repeat from program to program;
- an operation's *scope* is the INNERMOST of the program's scopes in its
  ``tf_op``: ``optim/<solver>/<step>`` (three segments) or
  ``agg|fe|re|cd|serve/<name>`` (two), found wherever it stands, inside a
  transform's wrapper too (``vmap(optim/newton/factor_solve)``). An
  operation with none is ``unscoped``: eager one-operation programs such as
  ``jit(convert_element_type)``, and what the compiler inserts and names
  after nothing (a re-layout ``copy``);
- its *bucket* is the ``re/b<index>`` segment, wherever it stands, of its
  program (``program_id``: each random effect's ladder is a program);
- ``bytes`` are the metadata's ``bytes_accessed`` times the operation's
  executions in the window, for operations that enclose no other (a
  ``while``'s own figure repeats its body's).

    python -m benchmark.scope_reader <file.xplane.pb>     # the tables, as JSON
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import trace_reader

UNSCOPED = "unscoped"
_SCOPE = re.compile(r"(?<![A-Za-z0-9_])(?:optim/[a-z0-9_]+/[a-z0-9_]+"
                    r"|(?:agg|fe|re|cd|serve)/[a-z0-9_]+)")
_BUCKET = re.compile(r"(?<![A-Za-z0-9_])re/b(\d+)(?![A-Za-z0-9_])")


@dataclasses.dataclass
class Op:
    """One HLO instruction of one program, over the window."""

    name: str           # as XLA prints it
    path: str           # its tf_op; "" where the compiler gave it none
    category: str       # hlo_category
    program: int        # program_id: which compiled program it belongs to
    seconds: float      # self seconds, mean over the chips used
    bytes: float        # bytes_accessed x executions (0 if it encloses others)


def scope_of(path: str) -> str:
    """The innermost of the program's scopes in a ``tf_op``."""
    found = _SCOPE.findall(path)
    return found[-1] if found else UNSCOPED


def bucket_of(path: str) -> Optional[int]:
    found = _BUCKET.search(path)
    return int(found.group(1)) if found else None


# --------------------------------------------------------------------------
# the file
# --------------------------------------------------------------------------

def _xspace_class():
    """The message class of ``XSpace`` (tsl/profiler/protobuf/xplane.proto),
    down to the fields read here."""
    from google.protobuf import (
        descriptor_pb2,
        descriptor_pool,
        message_factory,
    )

    package = "photon_tpu_benchmark_xplane"
    file = descriptor_pb2.FileDescriptorProto(
        name=f"{package}.proto", package=package, syntax="proto3")
    field = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": field.TYPE_INT64, "uint64": field.TYPE_UINT64,
              "double": field.TYPE_DOUBLE, "string": field.TYPE_STRING}
    messages = {
        "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
                  ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
                  ("str_value", 5, "string"), ("ref_value", 7, "uint64")],
        "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
                   ("duration_ps", 3, "int64")],
        "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
                  ("events", 4, "*XEvent")],
        "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                           ("stats", 5, "*XStat")],
        "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
        # a map<int64, Message> field is a repeated (key, value) entry
        "EventMetadataEntry": [("key", 1, "int64"),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "int64"),
                              ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, "string"), ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EventMetadataEntry"),
                   ("stat_metadata", 5, "*StatMetadataEntry")],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    for name, fields in messages.items():
        message = file.message_type.add(name=name)
        for fname, number, kind in fields:
            f = message.field.add(
                name=fname, number=number,
                label=(field.LABEL_REPEATED if kind.startswith("*")
                       else field.LABEL_OPTIONAL))
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type = field.TYPE_MESSAGE
                f.type_name = f".{package}.{kind.lstrip('*')}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.XSpace"))


def _enclose_nothing(events: Sequence[Tuple[float, float, int]]
                     ) -> Dict[int, int]:
    """id -> executions, over one line's (nested) events, that enclose no
    other event."""
    ordered = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    counts: Dict[int, int] = {}
    for (_, end, key), following in zip(ordered, ordered[1:] + [None]):
        if following is None or following[0] >= end:
            counts[key] = counts.get(key, 0) + 1
    return counts


def _described(meta, stat_name: Dict[int, str]) -> Tuple[str, str, int, int]:
    """(tf_op, hlo_category, program_id, bytes_accessed) of one operation's
    metadata; a string may be stored by reference to a stat's name."""
    stats = {stat_name.get(s.metadata_id): s for s in meta.stats}

    def text(name):
        s = stats.get(name)
        return "" if s is None else (
            s.str_value or stat_name.get(s.ref_value, ""))

    def number(name):
        s = stats.get(name)
        return 0 if s is None else (s.int64_value or s.uint64_value)

    return (text("tf_op"), text("hlo_category"), number("program_id"),
            number("bytes_accessed"))


def read(path: str, window: Optional[Tuple[float, float]] = None) -> List[Op]:
    """Every operation that ran in the window, with its self seconds.
    ``window`` is (start ns, end ns) on the trace's clock; left out, it is
    read from the file's ``bench/window`` annotation."""
    if window is None:
        window = trace_reader.read(path).window
    lo, hi = window
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())

    chips = []
    for plane in space.planes:
        if not plane.name.startswith(trace_reader.DEVICE_PLANE):
            continue
        events = []
        for line in plane.lines:
            if line.name != trace_reader.OPS_LINE:
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps / 1000.0
                end = start + ev.duration_ps / 1000.0
                if end > lo and start < hi:
                    events.append((max(start, lo), min(end, hi),
                                   ev.metadata_id))
        if events:
            chips.append((plane, events))

    merged: Dict[Tuple[str, str, int], Op] = {}
    for plane, events in chips:
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        metadata = {e.key: e.value for e in plane.event_metadata}
        leaves = _enclose_nothing(events)
        # trace_reader's own arithmetic, keyed by metadata id, not by name
        for key, seconds in trace_reader.self_times(events).items():
            meta = metadata[key]
            path_, category, program, nbytes = _described(meta, stat_name)
            op = merged.setdefault(
                (meta.name, path_, program),
                Op(meta.name, path_, category, program, 0.0, 0.0))
            op.seconds += seconds / len(chips)
            op.bytes += float(nbytes) * leaves.get(key, 0) / len(chips)
    return sorted(merged.values(), key=lambda op: -op.seconds)


def of(run) -> Optional[List[Op]]:
    """The operations of a run's traced window, parsed once a run (the
    readers under ``layer_metrics/`` share it), or None without a trace."""
    if run.trace is None or not run.trace.ops:
        return None
    found = getattr(run.trace, "scoped_ops", None)
    if found is None:
        path = trace_reader.find_xplane(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "out",
            run.cell["name"], "trace"))
        found = run.trace.scoped_ops = read(path, run.trace.window)
    return found


# --------------------------------------------------------------------------
# the reductions
# --------------------------------------------------------------------------

def by_scope(ops: Iterable[Op]) -> Dict[str, List[float]]:
    """scope -> [seconds, bytes], by each operation's innermost scope."""
    out: Dict[str, List[float]] = {}
    for op in ops:
        entry = out.setdefault(scope_of(op.path), [0.0, 0.0])
        entry[0] += op.seconds
        entry[1] += op.bytes
    return dict(sorted(out.items(), key=lambda kv: -kv[1][0]))


def by_bucket(ops: Iterable[Op]) -> Dict[Tuple[int, int], List[float]]:
    """(program, bucket index) -> [seconds, bytes], over the operations
    under a ``re/b<index>`` wherever it stands in their path. Every random
    effect's ladder numbers its buckets from 0 and one jitted function may
    serve several of them, so the program tells the coordinates apart
    (``factor_solve``'s matrix shape in an operation's name says which)."""
    out: Dict[Tuple[int, int], List[float]] = {}
    for op in ops:
        bucket = bucket_of(op.path)
        if bucket is not None:
            entry = out.setdefault((op.program, bucket), [0.0, 0.0])
            entry[0] += op.seconds
            entry[1] += op.bytes
    return dict(sorted(out.items()))


def under(ops: Iterable[Op], *scopes: str) -> float:
    """Seconds of the operations with one of ``scopes`` ANYWHERE in their
    path: a scope with everything nested in it."""
    return sum(op.seconds for op in ops
               if not set(_SCOPE.findall(op.path)).isdisjoint(scopes))


def share(ops: Sequence[Op], *prefixes: str) -> Optional[float]:
    """Per cent of the window's busy seconds whose innermost scope starts
    with one of ``prefixes`` (``unscoped`` is a scope of its own here).
    None where the trace has no scope of the program's at all: a program
    from before it named anything is not 100% unscoped, it is unread."""
    scopes = by_scope(ops)
    busy = sum(s for s, _ in scopes.values())
    if not busy or set(scopes) <= {UNSCOPED}:
        return None
    return 100.0 * sum(s for name, (s, _) in scopes.items()
                       if name.startswith(prefixes)) / busy


def main(argv=None) -> int:
    import json
    import sys

    paths = sys.argv[1:] if argv is None else argv
    if len(paths) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    ops = read(paths[0])
    print(json.dumps({
        "busy_s": sum(op.seconds for op in ops),
        "by_scope": by_scope(ops),
        "by_bucket": {f"{program}/b{bucket}": v
                      for (program, bucket), v in by_bucket(ops).items()},
        "unscoped": [[op.name[:200], op.category, op.seconds] for op in ops
                     if scope_of(op.path) == UNSCOPED][:20],
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
