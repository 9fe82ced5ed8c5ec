"""Medians and quartile spreads over the runs of one or two sets.

    python3 benchmark/tools/spread.py DIR [DIR2]

DIR holds the logs ``many.py`` wrote (their last lines are read).  For
each workload and metric: the median, and the spread the contract sets a
bound from (distance between first and third quartile as
``statistics.quantiles(values, n=4)`` gives them, over the median).  With
two directories, the wider of the two spreads, five times it, and how far
the second set's median lies from the first's.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness.stats import quartile_spread  # noqa: E402


def read(folder):
    out = {}
    for path in sorted(glob.glob(os.path.join(folder, "*.log"))):
        with open(path) as fh:
            lines = fh.read().splitlines()
        try:
            res = json.loads(lines[-1])
        except (ValueError, IndexError):
            print(f"{path}: no result")
            continue
        workload = os.path.basename(path).split("-", 1)[1].rsplit("-s", 1)[0]
        if not res["correct"]:
            print(f"{path}: correct is false")
        for name, m in res.get("metrics", {}).items():
            out.setdefault((workload, name), []).append(m["value"])
    return out


def main() -> int:
    sets = [read(folder) for folder in sys.argv[1:3]]
    for key in sorted(sets[0]):
        rows = [s.get(key, []) for s in sets]
        text = f"{key[0]:28s} {key[1]:18s}"
        spreads = []
        for values in rows:
            if len(values) >= 2:
                spreads.append(quartile_spread(values))
                text += f" n={len(values)} median={statistics.median(values):.4f} spread={spreads[-1]:.4%}"
        if spreads:
            text += f" | widest={max(spreads):.4%} x5={5 * max(spreads):.4%}"
        if len(rows) == 2 and rows[0] and rows[1]:
            a, b = statistics.median(rows[0]), statistics.median(rows[1])
            text += f" | second/first median {b / a - 1:+.4%}"
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
