"""Share of the traced window's busy device seconds in collective
operations: all-reduce, all-gather, reduce-scatter, collective-permute and
all-to-all, their asynchronous ``-start`` / ``-done`` halves included, by
each operation's own self seconds (``benchmark/scope_reader.py``: the mean
over the chips that ran anything, of the collective seconds over the busy
seconds). What the mesh's communication costs a fit where it is not hidden
behind other work. The run's log splits it by innermost scope
(``cd/whole_score``: the flat score made whole for the next residual;
``agg/*``: the fixed effect's all-reduced sums; ``re/*``: the per-entity
tables). ``None`` without a trace."""

import re

from benchmark import scope_reader

LAYER = "collectives"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
# ``%name = shape opcode(operands)``: the opcode is the first lower-case
# word that opens a parenthesis after the shape (a layout's ``T(8,128)``
# is upper case)
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def is_collective(op) -> bool:
    """By the operation's opcode or its HLO category, never by an operand
    that a collective produced."""
    head, _, rest = op.name.partition(" = ")
    found = _OPCODE.search(" " + rest)
    words = (head.lstrip("%"), found.group(1) if found else "", op.category)
    return any(w.startswith(kind) for w in words for kind in KINDS)


def by_scope(ops):
    """innermost scope -> collective seconds. A collective the compiler
    left unnamed (a TPU turns some all-gathers into all-reduces of a
    zero-padded buffer and drops their scope) is named after its program's
    commonest scope: ``unscoped in <scope>``."""
    commonest = {}
    for op in ops:
        scope = scope_reader.scope_of(op.path)
        if scope != scope_reader.UNSCOPED:
            seen = commonest.setdefault(op.program, {})
            seen[scope] = seen.get(scope, 0) + 1
    out = {}
    for op in ops:
        if is_collective(op):
            scope = scope_reader.scope_of(op.path)
            if scope == scope_reader.UNSCOPED and commonest.get(op.program):
                seen = commonest[op.program]
                scope = f"unscoped in {max(seen, key=seen.get)}"
            out[scope] = out.get(scope, 0.0) + op.seconds
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def read(run):
    ops = scope_reader.of(run)
    busy = sum(op.seconds for op in ops) if ops else 0.0
    if not busy:
        return None
    split = by_scope(ops)
    print(f"[bench] collective seconds by innermost scope (of {busy:.4f} "
          f"busy): {split}", flush=True)
    return 100.0 * sum(split.values()) / busy
