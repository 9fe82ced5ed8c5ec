"""What a run prints: lines that name the device, and the last line."""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Optional


def exact(name: str, value) -> Dict[str, Any]:
    """One number of the comparison that decides ``correct``, with the
    limit 0: an exact comparison."""
    return {"name": name, "value": int(value), "limit": 0, "ok": int(value) == 0}


class Reporter:
    """Every line before the last names platform, device kind and count,
    so that a number cut out of a log still says where it was read."""

    def __init__(self, t_start: float, device: Dict[str, Any], rehearse: bool):
        self.t_start = t_start
        self.tag = f"{device['platform']}/{device['kind']}/x{device['count']}"
        if rehearse:
            self.tag += " REHEARSAL"

    def say(self, msg: str) -> None:
        print(f"[{time.perf_counter() - self.t_start:7.1f}s {self.tag}] {msg}", flush=True)

    def phase(self, name: str, seconds: float, extra: str = "") -> None:
        self.say(f"set-up {name}: {seconds:.2f}s{(' ' + extra) if extra else ''}")


def result_line(
    *,
    correct: bool,
    attempted: int,
    failed: int,
    metrics: Dict[str, Dict[str, Any]],
    device: Dict[str, Any],
    breakdown: Optional[Dict[str, Any]] = None,
    rehearsal: bool = False,
    control: bool = False,
) -> str:
    """The last line of standard output: one JSON object.  A rehearsal's
    numbers go under ``rehearsed_metrics``, never under ``metrics``: a CPU
    run reports no device metric."""
    out: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        ("rehearsed_metrics" if rehearsal else "metrics"): metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    if rehearsal:
        out["rehearsal"] = True
    if control:
        out["control"] = True
    return json.dumps(out)
