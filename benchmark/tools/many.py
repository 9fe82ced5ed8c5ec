"""Several benchmark runs in one call, one process each, one after another.

    python3 benchmark/tools/many.py [--out DIR] [--copy-trace] RUN [RUN ...]

RUN is ``workload:seed:seconds:trace[:flag,...]`` (flags: ``control``,
``rehearse``).  This parent never touches JAX, so it never holds the chip
its children need.  Each child's output goes to ``DIR/<n>-<workload>.log``
(default ``chiprun_out/runs``), its last line is echoed, and a table of
the results ends the output.  With ``--copy-trace`` a traced run's
``.xplane.pb`` is copied beside its log.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "runs"))
    ap.add_argument("--copy-trace", action="store_true")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    rows, worst = [], 0
    for i, run in enumerate(args.runs):
        workload, seed, seconds, trace, *flags = run.split(":")
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", workload, "--seed", seed, "--seconds", seconds, "--trace", trace]
        for flag in (flags[0].split(",") if flags else []):
            cmd.append("--" + flag)
        log = os.path.join(args.out, f"{i:02d}-{workload}-s{seed}-t{trace}.log")
        t0 = time.perf_counter()
        with open(log, "w") as fh:
            rc = subprocess.run(cmd, cwd=ROOT, stdout=fh, stderr=subprocess.STDOUT).returncode
        wall = time.perf_counter() - t0
        worst = max(worst, rc)
        with open(log) as fh:
            lines = fh.read().splitlines()
        last = lines[-1] if lines else ""
        print(f"--- {run} rc={rc} wall={wall:.1f}s log={log}")
        for line in lines:
            if "set-up" in line or "check " in line or "window " in line or "trace:" in line \
                    or "FAILED" in line or "Error" in line:
                print("    " + line)
        print("    " + last, flush=True)
        try:
            rows.append((run, rc, wall, json.loads(last)))
        except ValueError:
            rows.append((run, rc, wall, None))
        if args.copy_trace and trace == "1":
            for path in glob.glob(os.path.join(ROOT, ".bench_out", "trace-" + workload,
                                               "plugins", "profile", "*", "*.xplane.pb")):
                shutil.copy(path, log[:-4] + ".xplane.pb")
    print("=== results")
    for run, rc, wall, res in rows:
        if res is None:
            print(f"{run} rc={rc} wall={wall:.0f}s NO RESULT")
            continue
        vals = {k: round(v["value"], 4) for k, v in
                (res.get("metrics") or res.get("rehearsed_metrics") or {}).items()}
        print(f"{run} rc={rc} wall={wall:.0f}s correct={res['correct']} "
              f"attempted={res['attempted']} failed={res['failed']} "
              f"peak={res['device'].get('memory_peak_bytes')} {json.dumps(vals)}")
    return worst


if __name__ == "__main__":
    sys.exit(main())
