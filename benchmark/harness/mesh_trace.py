"""Readers' helpers for a cell whose wake program runs on several chips.

``harness/trace.py seconds_of`` and ``harness/program_trace.py
ProgramTrace.seconds`` reduce the device planes of a trace to ONE number
by summing or averaging them: right for one chip, wrong for a mesh, whose
shards run one program in step and wait for each other in every
collective, so that a wake lasts as long as its slowest shard.  The
functions here keep the planes apart and take the MAXIMUM: the pace of
the slowest shard.  They read what those two files parsed (``obs.trace``,
``program_trace(obs)``) and return ``None`` where there is nothing to
read (no traced run, no whole wake in the traced interval, a program
that names no such scope).
"""

from __future__ import annotations

from typing import List, Optional

from .program_trace import KERNEL_EVENT, WAKE_SCOPE, program_trace, window_wake_stats


def kernel_ms_slowest_plane(obs) -> Optional[float]:
    """Per wake, the largest over the device planes of the summed
    durations of the propagate kernel's events (`` custom-call(``, as
    ``layers/kernel_ms.py`` finds them)."""
    trace = obs.trace
    if trace is None:
        return None
    wakes = trace.spans_inside("wake")
    per_plane = [
        sum(b - a for name, a, b in events if KERNEL_EVENT.search(name))
        for events in trace.device_events
    ]
    if not wakes or not per_plane or max(per_plane) <= 0:
        return None
    return max(per_plane) * 1e3 / wakes


def inside_scope(op, component: str) -> bool:
    """Is ``op`` INSIDE the named scope ``component`` of the wake
    program, that is: is ``component`` on its scope path and not the
    path's last part?  (The last part names the operation itself, and a
    lookup in a table is a ``gather`` too.)"""
    path = f"/{op.scope}"
    return f"/{WAKE_SCOPE}/" in path + "/" and f"/{component}/" in path


def scope_ms_slowest_plane(obs, component: str) -> Optional[float]:
    """Per wake, the largest over the device planes of the self seconds
    of the wake module's operations inside scope ``component``."""
    trace = program_trace(obs)
    if trace is None or obs.trace is None or not trace.wake_ops:
        return None
    wakes = obs.trace.spans_inside("wake")
    per_plane = [
        sum(op.own_s for op in ops if inside_scope(op, component))
        for ops in trace.wake_ops
    ]
    if not wakes or max(per_plane) <= 0:
        return None
    return max(per_plane) * 1e3 / wakes


def shard_wake_stats(obs) -> Optional[List[dict]]:
    """The counters of the window's wakes where the wake program is
    sharded (``MeshShadowGraph.wake_stats``: a shard's own counts as
    lists, what all shards decide alike once); ``None`` on a program
    whose counters are one chip's."""
    stats = window_wake_stats(obs)
    if not stats or not isinstance(stats[0].get("kernel_steps"), list):
        return None
    return stats
