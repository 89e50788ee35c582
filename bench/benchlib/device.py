"""The chips a run holds, their peaks, and their memory high-water mark."""
from __future__ import annotations

import json
from typing import Any, Dict, List

from benchlib.spec import BENCH


class NoChip(SystemExit):
    """Raised when JAX finds no TPU, or fewer chips than the cell asks
    for: the run prints no result and exits non-zero."""


def require_chips(count: int) -> List[Any]:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found platform "
                     f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < count:
        raise NoChip(f"bench: the cell needs {count} TPU chips, JAX found "
                     f"{len(devs)}")
    return devs[:count]


def peaks(device_kind: str) -> Dict[str, float]:
    """Per-chip peaks for ``device_kind``; an unknown device is an
    error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, where the backend reports
    it (0 where it does not, as on the CPU)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def describe(devs) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
