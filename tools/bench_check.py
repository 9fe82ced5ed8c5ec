#!/usr/bin/env python
"""bench-check: regression gate over the committed BENCH_* trajectory.

The repo commits one ``BENCH_<FAMILY>_rNN.json`` artifact per perf
round (FABRIC/SHARD/FOLD/WAKE families).  This tool parses each
family's trajectory, compares the newest run against the prior one
with per-family tolerance bands, and exits nonzero with a readable
delta table when a key metric regressed beyond its band — the cheap
"did this PR quietly lose the 50k frames/s" check the verify pass runs.

Semantics per metric direction:

- ``higher``  throughput-style: FAIL when new < prior * (1 - tol)
- ``lower``   latency-style:    FAIL when new > prior * (1 + tol)
- ``zero``    correctness tally (undercounts): FAIL when new > prior
- ``floor``   absolute minimum: FAIL when new < tol (no trajectory —
              an acceptance bar, e.g. partitioned/replicated >= 1.0)
- ``ceiling`` absolute maximum: FAIL when new > tol

A family with fewer than two committed runs is SKIPped (nothing to
compare), as is a metric whose path stopped existing — bench shapes
drift between rounds, and a missing key must read as "not comparable",
never as a silent pass of something that regressed.  Paths resolve
dotted (``link.batch.frames_per_sec``) with a one-level descent into
nested round documents (the r04 FOLD shape wraps the payload under
``"r4"``).

``--check-regression FILE`` runs the self-test the suite uses: the
given doctored newest-run copy must FAIL against the real trajectory.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


class Metric:
    __slots__ = ("path", "direction", "tolerance")

    def __init__(self, path: str, direction: str, tolerance: float):
        self.path = path
        self.direction = direction
        self.tolerance = tolerance


#: family -> (glob pattern, key metrics).  Tolerances are wide on
#: purpose: these runs come from whatever host the round ran on, and
#: the gate exists to catch step-function losses, not 5% jitter.
FAMILIES: Dict[str, Tuple[str, List[Metric]]] = {
    "FABRIC": (
        "BENCH_FABRIC_r*.json",
        [
            Metric("link.batch.frames_per_sec", "higher", 0.40),
            # r02+: the co-located shm + schema-codec path (the 250k/s
            # acceptance floor and the 500k ROADMAP target live here).
            # SKIPs against rounds that predate the mode.
            Metric("link.shm.frames_per_sec", "higher", 0.40),
            Metric("teardown.actors_per_sec", "higher", 0.40),
        ],
    ),
    "SHARD": (
        "BENCH_SHARD_r*.json",
        [
            Metric("steady.messages_per_sec", "higher", 0.40),
            Metric("post_rebalance_probe.undercounted_entities", "zero", 0.0),
        ],
    ),
    "FOLD": (
        "BENCH_FOLD_r*.json",
        [
            Metric("fold.packed.entries_per_sec", "higher", 0.40),
            Metric("sweep.garbage_actors_per_sec", "higher", 0.40),
        ],
    ),
    "WAKE": (
        "BENCH_WAKE_r*.json",
        [
            Metric("device_per_wake_ms", "lower", 0.40),
            Metric("sweeps_mean", "lower", 0.40),
        ],
    ),
    # Serving scenarios (tools/serving_bench.py): the chat-session
    # fleet through a rolling restart.  lost_acked is a hard zero —
    # a single acked command lost across drain/restart/die is a
    # durability regression, not jitter; restart p99 gets a wide band
    # (it includes rejoin rebalances on whatever host ran the round).
    "SCENARIO": (
        "BENCH_SCENARIO_r*.json",
        [
            Metric("steady.messages_per_sec", "higher", 0.40),
            Metric("restart.p99_latency_s", "lower", 0.60),
            # r02+: the arbiter's deliberate detection windows (settle
            # + reconnect probing) are reported separately as
            # recovery.detection_seconds; this per-entity figure
            # charges only the machinery after the LAST survivor
            # verdict, so the band stays a real regression gate even
            # though the scenario now runs a partition era first.
            Metric("recovery.seconds_per_entity", "lower", 0.60),
            Metric("ledger.lost_acked", "zero", 0.0),
            # r02+ (--partition): ack p99 through the split-brain +
            # heal window gets a wide band; dual activation — an
            # entity sampled live on the quarantined side AND a
            # survivor — is a hard zero, the fencing plane's whole
            # point.  Rounds predating the phase lack the keys and
            # SKIP honestly.
            Metric("partition.heal_p99_latency_s", "lower", 0.60),
            Metric("partition.dual_active_keys", "zero", 0.0),
        ],
    ),
    # Distributed collector (tools/dist_bench.py): 3-node partitioned
    # trace over cross-node garbage cycles.  leaked_actors is a hard
    # zero — a cycle the wave protocol cannot close is a soundness
    # regression, not jitter; throughput gets the usual wide band, and
    # the locality fraction is a structural property of the workload
    # (gated loosely so a full-replica regression — fraction ~1.0 —
    # fails while placement jitter passes).
    "DIST": (
        "BENCH_DIST_r*.json",
        [
            Metric("trace.garbage_actors_per_sec", "higher", 0.40),
            Metric("trace.leaked_actors", "zero", 0.0),
            # Authoritative slots only: a hub actor's owner also holds
            # bare mirrors of everything the hub references; since the
            # PR-15 mirror decay the RESIDENT fraction converges to
            # ~the owned fraction too, and both are gated — owned by
            # trajectory, resident by the absolute 0.7 acceptance bar.
            Metric("locality.max_node_owned_fraction", "lower", 0.60),
            # r02+ (the PR-15 communication-plane rebuild): the
            # partitioned trace must meet or beat the replicated fold
            # measured in the SAME run, termination must stay in the
            # 1-2 round regime, mark bytes get a trajectory band, and
            # the resident-population bar catches full-replica
            # regressions.  Rounds predating the keys SKIP honestly.
            Metric("trace.speedup_vs_replicated", "floor", 1.0),
            Metric("trace.rounds_per_wave", "ceiling", 2.5),
            Metric("trace.boundary_mark_bytes_per_wave", "lower", 0.60),
            Metric("locality.max_node_population_fraction", "ceiling", 0.70),
        ],
    ),
    # Ingress gateway (tools/ingress_bench.py): the front door under a
    # 10x-capacity overload storm plus a connection-scale phase.  The
    # contract is asymmetric on purpose: ADMITTED traffic keeps its p99
    # (absolute ceiling — the overload controller's whole point), SHED
    # traffic gets a clean retryable ERROR (floor on the clean-shed
    # fraction), and acked_then_lost is a hard zero from the debut
    # round — an ACK the client never got the result for is a
    # durability lie, not jitter.  Throughput/connection figures ride
    # the usual wide trajectory bands.
    "INGRESS": (
        "BENCH_INGRESS_r*.json",
        [
            Metric("overload.admitted_p99_ms", "ceiling", 250.0),
            Metric("overload.clean_shed_fraction", "floor", 0.95),
            Metric("overload.acked_then_lost", "zero", 0.0),
            Metric("overload.admitted_per_sec", "higher", 0.40),
            Metric("connections.per_gateway", "floor", 500.0),
            Metric("connections.connect_per_sec", "higher", 0.40),
        ],
    ),
    # Device plane (telemetry/device.py + tools/device_report.py): the
    # TPU-session artifacts gate the same figures the wake-budget
    # explainer decomposes.  Rounds that lack the device-figure keys
    # SKIP — a missing metric must never read as a pass.
    "DEVICE": (
        "BENCH_TPU_SESSION_r*.json",
        [
            Metric("device_per_wake_ms", "lower", 0.40),
            Metric("device_per_sweep_ms", "lower", 0.40),
            Metric("sweeps_mean", "lower", 0.40),
        ],
    ),
}


def _resolve(doc: Any, path: str) -> Optional[float]:
    """Dotted-path lookup; on a direct miss, descend one level into
    dict values looking for a sub-document where the full path
    resolves (the nested round shape)."""

    def direct(node: Any) -> Optional[float]:
        for part in path.split("."):
            if not isinstance(node, dict) or part not in node:
                return None
            node = node[part]
        if isinstance(node, bool):
            return float(node)
        if isinstance(node, (int, float)):
            return float(node)
        return None

    value = direct(doc)
    if value is not None:
        return value
    if isinstance(doc, dict):
        for sub in doc.values():
            if isinstance(sub, dict):
                value = direct(sub)
                if value is not None:
                    return value
    return None


def trajectory(repo: str, pattern: str) -> List[Tuple[int, str]]:
    """Sorted (round, path) pairs for one family."""
    out = []
    for path in glob.glob(os.path.join(repo, pattern)):
        match = _ROUND_RE.search(path)
        if match:
            out.append((int(match.group(1)), path))
    return sorted(out)


def _load(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    return doc if isinstance(doc, dict) else None


def compare_metric(
    metric: Metric, prior: Optional[float], new: Optional[float]
) -> Tuple[str, str]:
    """-> (status, note).  status in PASS/FAIL/SKIP."""
    if metric.direction in ("floor", "ceiling"):
        # Absolute acceptance bars: judged on the newest round alone
        # (the tolerance IS the bar), present-or-SKIP like any metric.
        if new is None:
            return "SKIP", "metric missing in newest"
        if metric.direction == "floor" and new < metric.tolerance:
            return "FAIL", f"below absolute floor {metric.tolerance:g}"
        if metric.direction == "ceiling" and new > metric.tolerance:
            return "FAIL", f"above absolute ceiling {metric.tolerance:g}"
        return "PASS", "absolute bar"
    if metric.direction == "zero" and new is not None and prior is None:
        # A correctness tally is an absolute floor, not a trajectory:
        # its FIRST round must already be zero — a nonzero debut would
        # otherwise grandfather itself in as the comparison baseline.
        if new > metric.tolerance:
            return "FAIL", "nonzero on its first round"
        return "PASS", "first round"
    if prior is None or new is None:
        return "SKIP", "metric missing in " + (
            "both" if prior is None and new is None
            else ("prior" if prior is None else "newest")
        )
    if metric.direction == "higher":
        floor = prior * (1.0 - metric.tolerance)
        if new < floor:
            return "FAIL", f"below floor {floor:.4g}"
        return "PASS", ""
    if metric.direction == "lower":
        ceiling = prior * (1.0 + metric.tolerance)
        if new > ceiling:
            return "FAIL", f"above ceiling {ceiling:.4g}"
        return "PASS", ""
    # zero: a correctness tally that must never grow
    if new > prior + metric.tolerance:
        return "FAIL", f"grew from {prior:g}"
    return "PASS", ""


def check_family(
    repo: str,
    family: str,
    newest_override: Optional[str] = None,
) -> List[Dict[str, Any]]:
    pattern, metrics = FAMILIES[family]
    runs = trajectory(repo, pattern)
    rows: List[Dict[str, Any]] = []
    if len(runs) < 2 and not (newest_override and runs):
        if not runs:
            rows.append(
                {
                    "family": family, "metric": "-", "status": "SKIP",
                    "note": "0 committed run(s); need 2",
                }
            )
            return rows
        # One committed round: no trajectory to band yet, but the
        # zero-direction correctness floors are absolute — they must
        # already hold on the debut round, or a nonzero tally would
        # grandfather itself in as the future comparison baseline.
        new_round, new_path = runs[-1]
        new_doc = _load(new_path)
        for metric in metrics:
            if metric.direction not in ("zero", "floor", "ceiling"):
                rows.append(
                    {
                        "family": family, "metric": metric.path,
                        "status": "SKIP",
                        "note": "1 committed run(s); need 2",
                    }
                )
                continue
            new = _resolve(new_doc, metric.path) if new_doc else None
            if new is None:
                status, note = "SKIP", "metric missing in newest"
            else:
                status, note = compare_metric(metric, None, new)
            rows.append(
                {
                    "family": family,
                    "metric": metric.path,
                    "prior": None,
                    "new": new,
                    "rounds": f"r{new_round:02d}",
                    "delta": "",
                    "tolerance": metric.tolerance,
                    "direction": metric.direction,
                    "status": status,
                    "note": note,
                }
            )
        return rows
    if newest_override:
        prior_round, prior_path = runs[-1]
        new_round, new_path = prior_round + 1, newest_override
    else:
        (prior_round, prior_path), (new_round, new_path) = runs[-2], runs[-1]
    prior_doc, new_doc = _load(prior_path), _load(new_path)
    for metric in metrics:
        prior = _resolve(prior_doc, metric.path) if prior_doc else None
        new = _resolve(new_doc, metric.path) if new_doc else None
        status, note = compare_metric(metric, prior, new)
        delta = ""
        if prior not in (None, 0) and new is not None:
            delta = f"{(new - prior) / prior * 100.0:+.1f}%"
        rows.append(
            {
                "family": family,
                "metric": metric.path,
                "prior": prior,
                "new": new,
                "rounds": f"r{prior_round:02d}->r{new_round:02d}",
                "delta": delta,
                "tolerance": metric.tolerance,
                "direction": metric.direction,
                "status": status,
                "note": note,
            }
        )
    return rows


def render_table(rows: List[Dict[str, Any]]) -> str:
    def num(v: Any) -> str:
        return f"{v:.4g}" if isinstance(v, float) else "-"

    widths = (7, 44, 12, 12, 8, 11, 6)
    header = ("family", "metric", "prior", "new", "delta", "rounds", "status")
    lines = [
        "  ".join(h.ljust(w) for h, w in zip(header, widths)),
        "  ".join("-" * w for w in widths),
    ]
    for row in rows:
        cells = (
            row["family"],
            row["metric"],
            num(row.get("prior")),
            num(row.get("new")),
            row.get("delta", "") or "-",
            row.get("rounds", "-"),
            row["status"],
        )
        line = "  ".join(str(c).ljust(w) for c, w in zip(cells, widths))
        if row.get("note"):
            line += f"  ({row['note']})"
        lines.append(line)
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="bench-check", description=__doc__.splitlines()[0]
    )
    parser.add_argument(
        "--repo",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="repo root holding the BENCH_*.json trajectory",
    )
    parser.add_argument(
        "--family",
        choices=sorted(FAMILIES),
        action="append",
        help="check only these families (default: all)",
    )
    parser.add_argument(
        "--check-regression",
        metavar="FILE",
        help="treat FILE as the newest run of its family (self-test: a "
        "doctored copy must FAIL)",
    )
    parser.add_argument(
        "--json", action="store_true", help="emit the rows as JSON"
    )
    args = parser.parse_args(argv)

    families = args.family or sorted(FAMILIES)
    override_family = None
    if args.check_regression:
        base = os.path.basename(args.check_regression)
        for name, (pattern, _metrics) in FAMILIES.items():
            if base.startswith(pattern.split("_r")[0]):
                override_family = name
        if override_family is None:
            print(
                f"bench-check: cannot infer family of {base!r}",
                file=sys.stderr,
            )
            return 2
        families = [override_family]

    rows: List[Dict[str, Any]] = []
    for family in families:
        rows.extend(
            check_family(
                args.repo,
                family,
                newest_override=(
                    args.check_regression if family == override_family else None
                ),
            )
        )
    if args.json:
        print(json.dumps(rows, indent=2, sort_keys=True))
    else:
        print(render_table(rows))
    failed = [r for r in rows if r["status"] == "FAIL"]
    if failed:
        print(
            f"bench-check: {len(failed)} metric(s) regressed beyond tolerance",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
