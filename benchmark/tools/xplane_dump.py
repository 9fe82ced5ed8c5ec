"""Look at one trace by hand: planes, lines, and the names that take the
most time on each line.

    python3 benchmark/tools/xplane_dump.py <file.xplane.pb> [top]
"""

from __future__ import annotations

import sys


def main() -> int:
    import jax

    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            by_name, n, lo, hi = {}, 0, None, None
            for e in line.events:
                n += 1
                by_name[e.name] = by_name.get(e.name, 0.0) + e.duration_ns
                lo = e.start_ns if lo is None else min(lo, e.start_ns)
                hi = e.end_ns if hi is None else max(hi, e.end_ns)
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, {lo * 1e-9:.4f}s .. {hi * 1e-9:.4f}s")
            for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]:
                print(f"      {ns * 1e-9:10.6f}s  {name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
