"""Host + device memory watermark sampling, one sample per phase.

Host numbers come from ``/proc/self/status`` (VmRSS current, VmHWM
lifetime peak) with a ``resource.getrusage`` fallback; device numbers
from ``Device.memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use``
where the backend reports them — TPU does, CPU usually returns None).

Sampling is pulled, never pushed: :func:`record_phase` runs where a
driver closes its root span (``train``, ``score``) and at
RunReport build time — a few /proc reads per driver run, nothing per
iteration, nothing inside jit. A span samples nothing by itself: whatever
happens to be outermost (``cd/sweep`` when a library user fits under a
profiler) would otherwise read /proc and ``memory_stats()`` every sweep.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List, Optional

_LOCK = threading.Lock()
_PHASE_SAMPLES: Dict[str, Dict[str, Any]] = {}  # phase -> last sample


def host_memory() -> Dict[str, int]:
    """{"rss_bytes", "peak_rss_bytes"} for this process."""
    rss = peak = None
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    rss = int(line.split()[1]) * 1024
                elif line.startswith("VmHWM:"):
                    peak = int(line.split()[1]) * 1024
    except OSError:
        pass
    if rss is None or peak is None:  # non-Linux fallback
        try:
            import resource
            ru = resource.getrusage(resource.RUSAGE_SELF)
            # ru_maxrss is KiB on Linux, bytes on macOS; Linux handled above
            peak = peak if peak is not None else ru.ru_maxrss * 1024
            rss = rss if rss is not None else peak
        except Exception:  # pragma: no cover - last resort
            rss = rss or 0
            peak = peak or 0
    return {"rss_bytes": int(rss), "peak_rss_bytes": int(peak)}


def device_memory() -> List[Dict[str, Any]]:
    """Per-local-device allocator stats; [] when jax isn't loaded or the
    backend doesn't report them. Never initializes a backend on its own
    (only reads stats if jax is already imported AND a backend exists)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return []
    out: List[Dict[str, Any]] = []
    try:
        devices = jax.local_devices()
    except Exception:  # backend not initialized / unavailable
        return []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out.append({
            "device": str(d),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
        })
    return out


def sample() -> Dict[str, Any]:
    return {"unix": time.time(), "host": host_memory(),
            "devices": device_memory()}


def record_phase(phase: str) -> Optional[Dict[str, Any]]:
    """Store the watermark sample for a named phase (last sample wins:
    VmHWM / peak_bytes_in_use are lifetime-cumulative, so the sample at
    phase END is the watermark as of that phase)."""
    from photon_tpu.obs import _config
    if not _config.enabled():
        return None
    s = sample()
    with _LOCK:
        _PHASE_SAMPLES[phase] = s
    return s


def watermarks() -> Dict[str, Dict[str, Any]]:
    with _LOCK:
        return {k: dict(v) for k, v in _PHASE_SAMPLES.items()}


def clear() -> None:
    with _LOCK:
        _PHASE_SAMPLES.clear()
