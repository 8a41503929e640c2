"""Native (C) runtime components, built on demand with the system compiler.

The reference's runtime rides the JVM (Breeze/Spark/PalDB all JIT-compiled);
this package is the equivalent native layer for the TPU build's HOST side —
currently the Avro binary block decoder that feeds ingest
(``photon_tpu/io/avro.py``). Everything here is optional: import failures
or compile failures degrade to the pure-Python implementations.

Build: a single ``cc -O2 -shared -fPIC`` invocation against the running
interpreter's headers, cached next to the source under a name that
carries the source's hash; no pip, no setuptools.
Set ``PHOTON_TPU_NO_NATIVE=1`` to disable entirely.
"""

from __future__ import annotations

import hashlib
import logging
import os
import subprocess
import sys
import sysconfig
from typing import Any, Optional, Tuple

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SENTINEL_BROKEN = object()
_mods: dict = {}          # stem -> module | _SENTINEL_BROKEN


def _build_extension(stem: str) -> Optional[str]:
    """Compile <stem>.c -> _<stem>-<source sha>.<ext_suffix> next to the
    source. Returns the path, or None when no compiler / unwritable
    directory. The built file is keyed on a hash of the source it was
    built from: a copied or checked-out tree keeps no mtime promise, and
    a stale .so must never be loaded for a newer .c."""
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    src = os.path.join(_DIR, f"{stem}.c")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    out = os.path.join(_DIR, f"_{stem}-{digest}{suffix}")
    if os.path.exists(out):
        return out
    include = sysconfig.get_paths()["include"]
    cc = os.environ.get("CC", "cc")
    # compile to a process-unique temp path and rename into place:
    # concurrent first runs must never truncate a .so another process has
    # already mapped (SIGBUS), and a half-written file must never be
    # importable; rename is atomic on the same filesystem
    tmp = f"{out}.build-{os.getpid()}"
    cmd = [cc, "-O2", "-shared", "-fPIC", f"-I{include}", src, "-o", tmp]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            logger.warning("native %s build failed:\n%s", stem,
                           r.stderr[-2000:])
            return None
        os.replace(tmp, out)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.info("native %s build unavailable: %r", stem, e)
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
    return out


def _load_ext(stem: str):
    cached = _mods.get(stem)
    if cached is not None:
        return None if cached is _SENTINEL_BROKEN else cached
    if os.environ.get("PHOTON_TPU_NO_NATIVE"):
        _mods[stem] = _SENTINEL_BROKEN
        return None
    path = _build_extension(stem)
    if path is None:
        _mods[stem] = _SENTINEL_BROKEN
        return None
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            f"photon_tpu.native._{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _mods[stem] = mod
        return mod
    except Exception as e:  # noqa: BLE001 — optional accelerator
        logger.warning("native %s load failed: %r", stem, e)
        _mods[stem] = _SENTINEL_BROKEN
        return None


def _load():
    return _load_ext("avrodec")


def libsvm_parser():
    """The native LibSVM tokenizer (libsvmdec.c), or None. Returns a
    callable ``parse(data: bytes, zero_based: int) -> (labels, indptr,
    cols, vals)`` raw little-endian buffers (f64 / i64 / i32 / f64)."""
    mod = _load_ext("libsvmdec")
    return None if mod is None else mod.parse


# -- schema program compiler --------------------------------------------------

_PRIM_OPS = {"null": (0,), "boolean": (1,), "int": (2,), "long": (2,),
             "float": (3,), "double": (4,), "bytes": (5,), "string": (6,)}


def _program_of(schema, names, ns, depth=0) -> Tuple:
    """Resolved schema (photon_tpu.io.avro _Names conventions) -> opcode
    tree for the C decoder. Raises ValueError on anything unsupported
    (caller falls back to the Python decoder)."""
    if depth > 48:
        raise ValueError("schema too deep (recursive types unsupported)")
    schema = names.resolve(schema, ns)
    if isinstance(schema, list):
        return (11, tuple(_program_of(b, names, ns, depth + 1)
                          for b in schema))
    if isinstance(schema, str):
        if schema in _PRIM_OPS:
            return _PRIM_OPS[schema]
        raise ValueError(f"unresolved named type {schema!r}")
    t = schema["type"]
    if t in _PRIM_OPS:
        return _PRIM_OPS[t]
    if t == "record":
        rec_ns = schema.get("namespace", ns)
        return (12, tuple(
            (f["name"], _program_of(f["type"], names, rec_ns, depth + 1))
            for f in schema["fields"]))
    if t == "enum":
        return (8, tuple(schema["symbols"]))
    if t == "fixed":
        return (7, int(schema["size"]))
    if t == "array":
        return (9, _program_of(schema["items"], names, ns, depth + 1))
    if t == "map":
        return (10, _program_of(schema["values"], names, ns, depth + 1))
    raise ValueError(f"unsupported schema {t!r}")


class BlockDecoder:
    """Compiled native decoder for one (schema, names) pair; ``None``-like
    (falsy) when the native path is unavailable for this schema."""

    def __init__(self, schema, names, ns=None):
        self._program = None
        mod = _load()
        if mod is None:
            return
        try:
            tree = _program_of(schema, names, ns)
            self._program = mod.compile_program(tree)
            self._decode = mod.decode_block
        except ValueError as e:
            logger.info("native decoder unavailable for schema: %s", e)
            self._program = None

    def __bool__(self) -> bool:
        return self._program is not None

    def decode_block(self, raw: bytes, count: int) -> list:
        return self._decode(self._program, raw, count)
