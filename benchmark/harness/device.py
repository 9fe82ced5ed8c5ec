"""The device a run is on: what JAX reports, the table of peaks, memory."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def device_info() -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def peaks_for(kind: str) -> Dict[str, Any]:
    """The published peaks of ``kind``; a device that is not in the table
    is an error, not a default."""
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in {PEAKS_FILE}: add it with its source")
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    reports no memory statistics, as the CPU does)."""
    import jax

    peak = 0
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
