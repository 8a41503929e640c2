"""Fleet router driver: JSONL requests -> entity-sharded serving fleet.

The front-end of the entity-sharded fleet (``serving/fleet.py``): scores
fixed effects locally and routes each request's random-effect lookups to
the shard that owns the entity under the canonical partitioner
(``parallel/partition.entity_shard`` — the same hash that split the cold
stores). Line protocol matches ``cli/serve`` (``ScoreRequest.from_json``
in, ``ScoreResponse.to_json`` out), so a router drops in where a
single-host serve process ran.

Two shard attachments:

* default — in-process shards: one ``ServingEngine`` per shard inside
  this process (`LocalShardClient`), each over its own per-shard cold
  store and hot tier. One process, N isolated serving stacks: the
  single-host deployment of the fleet code path.
* ``--spawn-shards`` — one child ``cli/serve --fleet-manifest
  --shard-id K`` process per shard, attached over JSONL pipes
  (`PipeShardClient`). Process-level isolation: a shard crash is a
  routed ``SHARD_UNAVAILABLE`` degradation at the router, never an
  exception; per-shard metrics snapshots are pulled over the pipe
  (``{"control": "stats"}``) and merged via
  ``obs/metrics.merge_snapshots``. One process per chip: the router
  scores fixed effects with JAX, so when it runs on an accelerator it
  holds the chip and the children cannot have it — ``--spawn-shards``
  is then refused (`ShardSpawnRefused`) unless ``--shard-platform cpu``
  puts the children on CPU explicitly. Each child's stderr goes to
  ``<--shard-log-dir>/shard-K.stderr``; the stats output names both.

Control lines::

    {"control": "stats"}   -> fleet stats (per-shard + merged)
    {"control": "drain"}   -> drain and exit

Usage::

    python -m photon_tpu.cli.fleet_serve --fleet-manifest /path/to/fleet \
        [--spawn-shards [--shard-platform cpu] [--shard-log-dir logs]] \
        [--hedge-timeout-ms 5] [--stats-output stats.json] \
        < requests.jsonl > scores.jsonl
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import queue
import subprocess
import sys
import tempfile
import threading
import time
from typing import List, Optional, Sequence

logger = logging.getLogger("photon_tpu.fleet_serve")

_TICK_S = 0.05


class ShardSpawnRefused(RuntimeError):
    """``--spawn-shards`` cannot give every shard child a device."""


def shard_child_platform(requested: Optional[str],
                         parent_backend: str) -> Optional[str]:
    """``JAX_PLATFORMS`` for the shard children, or None to leave their
    environment exactly as inherited. CPU children are an explicit
    request, never a default; a router that holds an accelerator
    refuses to start children that would need the same chip (they would
    die at start-up and every request would come back a routed
    ``SHARD_UNAVAILABLE`` — fixed-effect-only scores, exit 0)."""
    if requested is not None:
        return requested
    if parent_backend != "cpu":
        raise ShardSpawnRefused(
            f"--spawn-shards refused: this router process runs on "
            f"{parent_backend!r} and holds the chip, and a chip belongs "
            f"to one process — shard children inheriting this environment "
            f"could not initialise it. Pass --shard-platform cpu to run "
            f"the children on CPU, or drop --spawn-shards for in-process "
            f"shards on the router's device.")
    return None


class PipeShardClient:
    """A fleet shard behind a child ``cli/serve`` process and two JSONL
    pipes. Implements the same client surface as `LocalShardClient`:
    ``serve`` returns None (never raises) when the child is dead or the
    response does not arrive in time — the router's typed-degradation
    signal. ``platform`` is the child's ``JAX_PLATFORMS`` (None = the
    parent's environment, untouched); the child's stderr is kept in
    ``log_dir``."""

    def __init__(self, shard_id: int, fleet_dir: str,
                 serve_args: Sequence[str] = (),
                 response_timeout_s: float = 30.0, *,
                 platform: Optional[str], log_dir: str):
        self.shard_id = int(shard_id)
        self.alive = True
        self.response_timeout_s = response_timeout_s
        self.platform = platform
        self.stderr_path = os.path.join(log_dir, f"shard-{shard_id}.stderr")
        self._lock = threading.Lock()
        env = dict(os.environ)
        if platform is not None:
            env["JAX_PLATFORMS"] = platform
        with open(self.stderr_path, "wb") as errf:
            self._proc = subprocess.Popen(
                [sys.executable, "-m", "photon_tpu.cli.serve",
                 "--fleet-manifest", fleet_dir, "--shard-id", str(shard_id),
                 *serve_args],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=errf, text=True, env=env)
        self._lines: "queue.Queue" = queue.Queue()
        threading.Thread(target=self._read, daemon=True,
                         name=f"shard{shard_id}-reader").start()

    def _read(self):
        try:
            for line in self._proc.stdout:
                self._lines.put(line)
        except ValueError:
            pass  # hygiene-ok: pipe closed during shutdown
        self._lines.put(None)

    def _roundtrip(self, lines: List[str], want: int,
                   deadline: float) -> Optional[List[dict]]:
        """Write lines, collect ``want`` response objects (None on child
        death / timeout). Caller holds the lock, so responses can only
        belong to this call."""
        try:
            self._proc.stdin.write("".join(lines))
            self._proc.stdin.flush()
        except (OSError, ValueError):
            return None
        out: List[dict] = []
        while len(out) < want:
            try:
                line = self._lines.get(timeout=max(
                    deadline - time.monotonic(), 0.001))
            except queue.Empty:
                return None
            if line is None:
                return None
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
        return out

    def serve(self, requests) -> Optional[list]:
        from photon_tpu.serving.types import (Fallback, FallbackReason,
                                              ScoreResponse)
        if not self.alive or self._proc.poll() is not None:
            return None
        with self._lock:
            if not self.alive:
                return None
            objs = self._roundtrip(
                [json.dumps(r.to_json() if hasattr(r, "to_json")
                            else _req_json(r)) + "\n" for r in requests],
                len(requests),
                time.monotonic() + self.response_timeout_s)
        if objs is None:
            return None
        by_uid = {o.get("uid"): o for o in objs}
        resps = []
        for r in requests:
            o = by_uid.get(r.uid)
            if o is None:
                return None
            resps.append(ScoreResponse(
                r.uid, o.get("score"), bool(o.get("degraded")),
                tuple(Fallback(FallbackReason(f["reason"]),
                               f.get("coordinate"), f.get("detail", ""))
                      for f in o.get("fallbacks", ()))))
        return resps

    def warmup(self) -> dict:
        # the child warms its own ladder at boot; confirm it is up by
        # round-tripping a stats control line
        s = self.stats_snapshot()
        return {"programs": 0, "seconds": 0.0,
                "child_ready": s is not None}

    def stats_snapshot(self) -> Optional[dict]:
        if not self.alive or self._proc.poll() is not None:
            return None
        with self._lock:
            objs = self._roundtrip([json.dumps({"control": "stats"}) + "\n"],
                                   1, time.monotonic() + self.response_timeout_s)
        return objs[0] if objs else None

    def kill(self) -> None:
        self.alive = False
        self._proc.kill()

    def revive(self) -> None:
        raise NotImplementedError("a killed shard process cannot revive; "
                                  "start a replacement client")

    def breaker_state(self) -> str:
        s = self.stats_snapshot()
        if not s:
            return "unreachable"
        return str(((s.get("stats") or {}).get("breaker") or {})
                   .get("state", "unknown"))

    def hot_hit_rate(self) -> Optional[float]:
        return None  # lives in the child's own stats snapshot

    def shutdown(self) -> None:
        self.alive = False
        try:
            self._proc.stdin.close()
        except (OSError, ValueError):
            pass  # hygiene-ok: child already gone
        try:
            self._proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self._proc.kill()


def _req_json(r) -> dict:
    out = {"uid": r.uid, "features": {
        sid: [[n, t, v] for n, t, v in rows]
        for sid, rows in r.features.items()},
        "ids": dict(r.entity_ids), "offset": r.offset}
    if r.timeout_s is not None:
        out["timeout_ms"] = r.timeout_s * 1000.0
    return out


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_tpu.fleet_serve",
        description="Route JSONL requests over an entity-sharded "
                    "serving fleet")
    p.add_argument("--fleet-manifest", required=True, metavar="FLEET_DIR",
                   help="fleet dir holding fleet-manifest.json + "
                        "per-shard cold stores (io/fleet_store)")
    p.add_argument("--model-input-directory", default=None,
                   help="override the manifest's model_dir (fixed "
                        "effects + index maps)")
    p.add_argument("--spawn-shards", action="store_true",
                   help="one child serve process per shard over JSONL "
                        "pipes (default: in-process shard engines)")
    p.add_argument("--shard-platform", default=None, choices=("cpu",),
                   help="JAX_PLATFORMS for --spawn-shards children "
                        "(default: the router's environment, which is "
                        "refused when the router holds an accelerator)")
    p.add_argument("--shard-log-dir", default=None, metavar="DIR",
                   help="where each --spawn-shards child's stderr is kept "
                        "(default: beside --stats-output, else a fresh "
                        "temporary directory, logged at start)")
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--hot-capacity", type=int, default=None,
                   help="two-tier hot rows per shard coordinate "
                        "(default: shard stores fully resident)")
    p.add_argument("--hedge-timeout-ms", type=float, default=None,
                   help="resubmit a shard hop not answered within this "
                        "(default: hedging off)")
    p.add_argument("--shard-timeout-ms", type=float, default=None,
                   help="per-hop ceiling for requests without their own "
                        "deadline (default: none)")
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--stats-output", default=None,
                   help="write fleet stats() JSON here at stream end")
    p.add_argument("--log-level", default="INFO")
    return p


def build_fleet(args: argparse.Namespace):
    from photon_tpu.io.fleet_store import read_fleet_manifest
    from photon_tpu.serving import (CoeffStoreConfig, FleetConfig,
                                    ServingConfig, ShardedServingFleet)
    from photon_tpu.serving.fleet import build_front_engine
    from photon_tpu.utils import compile_cache

    compile_cache.maybe_enable()
    serving = ServingConfig(
        max_batch=args.max_batch, max_wait_s=args.max_wait_ms / 1000.0,
        coeff_store=(CoeffStoreConfig(hot_capacity=args.hot_capacity)
                     if args.hot_capacity is not None else None))
    config = FleetConfig(
        serving=serving,
        shard_timeout_s=(args.shard_timeout_ms / 1000.0
                         if args.shard_timeout_ms is not None else None),
        hedge_timeout_s=(args.hedge_timeout_ms / 1000.0
                         if args.hedge_timeout_ms is not None else None))
    if not args.spawn_shards:
        return ShardedServingFleet.from_fleet_dir(
            args.fleet_manifest, config,
            model_dir=args.model_input_directory)
    import jax
    platform = shard_child_platform(args.shard_platform,
                                    jax.default_backend())
    log_dir = args.shard_log_dir or (
        os.path.dirname(os.path.abspath(args.stats_output))
        if args.stats_output else tempfile.mkdtemp(prefix="fleet-shards-"))
    os.makedirs(log_dir, exist_ok=True)
    logger.info("shard children: JAX_PLATFORMS=%s, stderr under %s",
                platform or "(inherited)", log_dir)
    manifest = read_fleet_manifest(args.fleet_manifest)
    from photon_tpu.serving.fleet import _load_base
    base, ordered = _load_base(manifest, args.model_input_directory)
    front = build_front_engine(manifest, config, base=base)
    serve_args = ["--max-batch", str(args.max_batch),
                  "--max-wait-ms", str(args.max_wait_ms)]
    if args.hot_capacity is not None:
        serve_args += ["--hot-capacity", str(args.hot_capacity)]
    if args.model_input_directory:
        serve_args += ["--model-input-directory",
                       args.model_input_directory]
    clients = [PipeShardClient(sh["shard_id"], args.fleet_manifest,
                               serve_args, platform=platform,
                               log_dir=log_dir)
               for sh in manifest["shards"]]
    coords = [(re.coordinate_id, re.random_effect_type) for re in ordered]
    return ShardedServingFleet(front, clients, coords, config)


def _fleet_stats(fleet) -> dict:
    """``fleet.stats()`` plus, for ``--spawn-shards``, what each child
    process runs on and where its stderr is."""
    stats = fleet.stats()
    children = {c.shard_id: {"platform": c.platform or "(inherited)",
                             "stderr": c.stderr_path}
                for c in fleet.clients if isinstance(c, PipeShardClient)}
    if children:
        stats["shard_children"] = children
    return stats


def run(args: argparse.Namespace, stdin=None, stdout=None) -> int:
    logging.basicConfig(
        level=args.log_level, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from photon_tpu.resilience import shutdown
    from photon_tpu.serving import ScoreRequest

    stdin = stdin if stdin is not None else sys.stdin
    stdout = stdout if stdout is not None else sys.stdout
    fleet = build_fleet(args)
    if not args.no_warmup:
        info = fleet.warmup()
        logger.info("fleet warmed: %s", info)
    shutdown.install()
    draining = threading.Event()
    shutdown.add_callback(lambda reason: draining.set())

    lines: "queue.Queue" = queue.Queue()

    def _read():
        try:
            for line in stdin:
                lines.put(line)
        except ValueError:
            pass  # hygiene-ok: stdin closed during interpreter exit
        lines.put(None)

    threading.Thread(target=_read, daemon=True,
                     name="fleet-stdin-reader").start()

    bad_lines = 0
    try:
        while not draining.is_set():
            try:
                line = lines.get(timeout=_TICK_S)
            except queue.Empty:
                continue
            if line is None:
                break
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError as e:
                bad_lines += 1
                logger.warning("bad request line skipped: %r", e)
                continue
            if isinstance(obj, dict) and "control" in obj:
                cmd = obj.get("control")
                if cmd == "stats":
                    stdout.write(json.dumps(
                        {"control": "stats", "ok": True,
                         "stats": _fleet_stats(fleet)}) + "\n")
                elif cmd == "drain":
                    stdout.write(json.dumps(
                        {"control": "drain", "ok": True}) + "\n")
                    stdout.flush()
                    break
                else:
                    stdout.write(json.dumps(
                        {"control": cmd, "ok": False,
                         "error": f"unknown control {cmd!r}"}) + "\n")
                stdout.flush()
                continue
            # router batch: this line plus whatever is already queued
            batch = []
            try:
                batch.append(ScoreRequest.from_json(obj))
            except (ValueError, KeyError, TypeError) as e:
                bad_lines += 1
                logger.warning("bad request line skipped: %r", e)
                continue
            while len(batch) < args.max_batch:
                try:
                    nxt = lines.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    lines.put(None)
                    break
                nxt = nxt.strip()
                if not nxt:
                    continue
                try:
                    nobj = json.loads(nxt)
                except ValueError:
                    bad_lines += 1
                    continue
                if isinstance(nobj, dict) and "control" in nobj:
                    lines.put(nxt + "\n")   # controls between batches
                    break
                try:
                    batch.append(ScoreRequest.from_json(nobj))
                except (ValueError, KeyError, TypeError):
                    bad_lines += 1
            for resp in fleet.serve(batch):
                stdout.write(json.dumps(resp.to_json()) + "\n")
            stdout.flush()
    finally:
        stdout.flush()
        if args.stats_output:
            with open(args.stats_output, "w") as f:
                json.dump(_fleet_stats(fleet), f, indent=1)
                f.write("\n")
        fleet.shutdown()
        shutdown.uninstall()
    if bad_lines:
        logger.warning("%d malformed request lines skipped", bad_lines)
    return 0


def main(argv: Optional[list] = None) -> int:
    return run(build_arg_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
