"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, JAX imported once.  The cell is found by name in
``BENCHMARK.json``; its configuration names its driver
(``drivers/<driver>.py``), its traffic mix is ``traffic/<mix>.json`` and
each metric it reports has a reader of its own under ``metrics/`` (end to
end) or ``layers/`` (per layer).  This file and ``harness/`` name none of
them.

Set-up (generate from ``--seed``, pack, compile and warm every shape)
is timed as ``setup_s``; then the window runs for ``--seconds``; then,
outside both, the driver compares what the window produced with the
plain reference and prints each number compared beside its limit.  The
last line of standard output is the result, one JSON object.

There is no fallback: without a TPU (or with fewer chips than the cell
asks for) the run exits non-zero and prints no result.  ``--rehearse`` is
the CPU rehearsal of the on-chip-measurement guide: the ``rehearse``
sizes of the configuration and the traffic file, interpreted kernels,
and a last line marked as a rehearsal that carries no ``metrics``.
``--control`` switches on the driver's control, the run that the
comparison has to fail; its last line is marked too.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import faulthandler
import os
import sys
import traceback

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: what a run leaves behind (traces), inside the checkout and git-ignored
OUT_DIR = os.path.join(ROOT, ".bench_out")
#: A run that hangs says where: past these limits every thread's stack
#: goes to standard error and the process exits with 1 and no result.
#: Set-up may compile (the first run of a cell in a checkout); what
#: follows it (window, grace period, reference and comparison, trace
#: reduction) gets the window's length plus AFTER_SETUP_MAX_S.
SETUP_MAX_S = 1100
AFTER_SETUP_MAX_S = 150


def watchdog(seconds: float) -> None:
    """(Re-)arm the limit; the process's own standard error, also where a
    test has put another object in ``sys.stderr``'s place."""
    faulthandler.dump_traceback_later(seconds, exit=True, file=sys.__stderr__)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


class Context:
    """What the harness hands a driver."""

    def __init__(self, cell, args, obs, reporter):
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.seed = args.seed
        self.rehearse = args.rehearse
        self.control = args.control
        self.traced = bool(args.trace)
        self.obs = obs
        self.say = reporter.say
        self.phase = reporter.phase


def run(args, find_device=None) -> int:
    """Drive one run; returns the exit code.  ``find_device`` replaces
    the look for a chip (the tests' broken-path run goes through the
    rest of a run unchanged)."""
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before JAX loads
    for path in (ROOT, BENCH_DIR):
        if path not in sys.path:
            sys.path.insert(0, path)
    from harness import cell as cells
    from harness import device as devices
    from harness.compiles import CompileWatch
    from harness.obs import Obs
    from harness.report import Reporter, result_line
    from harness.stats import percentile, samples_beyond
    from harness.trace import TraceCapture

    cell = cells.load_cell(args.workload, rehearse=args.rehearse)
    seconds = args.seconds if args.seconds is not None else cell.run_seconds

    import jax

    # The program's one switch for the persistent cache: the directory
    # JAX_COMPILATION_CACHE_DIR names, else .jax_cache/ in this checkout.
    from uigc_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program, however quick to compile, so a second run loads all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    info = (find_device or devices.device_info)()
    reporter = Reporter(T_START, info, args.rehearse)
    say = reporter.say
    say(f"cell {cell.name} seed {args.seed} window {seconds}s trace {args.trace}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    if args.rehearse:
        if info["platform"] != "cpu":
            raise SystemExit("--rehearse runs on the CPU platform only")
    else:
        if info["platform"] == "cpu":
            raise SystemExit(f"no accelerator: JAX reports {info}")
        devices.peaks_for(info["kind"])  # an unknown kind is an error
    if info["count"] < cell.chips:
        raise SystemExit(f"the cell asks for {cell.chips} chip(s), JAX sees {info['count']}")

    obs = Obs()
    watch = CompileWatch()
    watch.install()
    driver = cells.load_driver(cell.config["driver"]).Driver(Context(cell, args, obs, reporter))
    try:
        driver.setup()
        watchdog(seconds + AFTER_SETUP_MAX_S)
        t_ready = time.perf_counter()
        setup_s = t_ready - T_START
        n_req, n_hit, s_req, _ = watch.between(T_START, t_ready)
        say(f"set-up total: {setup_s:.2f}s; compile requests {n_req} "
            f"({n_hit} loaded from the cache, {n_req - n_hit} compiled) {s_req:.2f}s; "
            f"device peak bytes {devices.memory_peak_bytes()}")

        capture = None
        if args.trace:
            length = float(cell.traffic.get("trace_seconds", 4.0))
            capture = TraceCapture(
                obs, os.path.join(OUT_DIR, "trace-" + cell.name),
                start_after_s=min(0.25 * seconds, max(0.0, seconds - length)),
                length_s=min(length, seconds),
            )
        t0 = obs.open_window()
        driver.window(seconds)
        t1 = obs.close_window()
        if capture is not None:
            capture.stop()
        n_req, n_hit, s_req, compiled = watch.between(t0, t1)
        obs.facts.update(setup_s=setup_s, compile_requests=n_req, compile_cache_hits=n_hit,
                         compile_s=s_req)
        peak = devices.memory_peak_bytes()
        say(f"window {obs.window_s:.3f}s; attempted {driver.attempted} failed {driver.failed}; "
            f"compile requests inside {n_req} ({n_hit} cache loads, {s_req:.2f}s){' ' + str(compiled) if compiled else ''}; "
            f"device peak bytes {peak}")
        for name in sorted(obs.samples):
            xs = obs.samples[name]
            say(f"  samples {name}: n={len(xs)} (beyond the 95th percentile: "
                f"{samples_beyond(len(xs), 95)}) p50={percentile(xs, 50):.3f} "
                f"p95={percentile(xs, 95):.3f} max={max(xs):.3f}"
                + (f" all={[round(x, 1) for x in xs]}" if len(xs) <= 32 else ""))
        for name in sorted(obs.spans):
            ms = obs.span_ms(name)
            say(f"  spans {name}: n={len(ms)} p50={percentile(ms, 50):.3f}ms max={max(ms):.3f}ms")
        for name in sorted(obs.counters):
            say(f"  counter {name}: {obs.counters[name]}")

        t0 = time.perf_counter()
        checks = driver.check()
        for c in checks:
            say(f"check {c['name']}: {c['value']} (limit {c['limit']}) "
                f"{'ok' if c['ok'] else 'NOT CORRECT'}")
        correct = bool(checks) and all(c["ok"] for c in checks)
        say(f"reference and comparison: {time.perf_counter() - t0:.2f}s; correct={correct}")
    finally:
        try:
            driver.close()
        finally:
            faulthandler.cancel_dump_traceback_later()

    metrics = {}
    for metric in cell.per_layer if args.trace else cell.end_to_end:
        value = metric.read(obs)
        if value is not None:
            metrics[metric.name] = {"value": value, "unit": metric.unit}
    device = dict(info, memory_peak_bytes=peak)
    breakdown = None
    if args.trace and obs.trace is not None:
        device.update(busy_s=obs.trace.busy_s, window_s=obs.trace.window_s)
        breakdown = {"device_ops": obs.trace.device_ops, "idle_gaps": obs.trace.idle_gaps}
        say(f"trace: {obs.trace.window_s:.3f}s traced, device busy {obs.trace.busy_s:.3f}s "
            f"on {obs.trace.n_devices} device plane(s); start+stop cost the host "
            f"{capture.overhead_s:.2f}s; {obs.facts.get('xplane')}")
    print(result_line(
        correct=correct, attempted=driver.attempted, failed=driver.failed,
        metrics=metrics, device=device, breakdown=breakdown,
        rehearsal=args.rehearse, control=args.control,
    ), flush=True)
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    watchdog(SETUP_MAX_S)
    try:
        return run(args)
    except SystemExit as e:
        if e.code not in (0, None):
            print(f"benchmark run FAILED: {e.code}", file=sys.stderr, flush=True)
            return 1 if not isinstance(e.code, int) else e.code
        return 0
    except BaseException:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the served runtime keeps dispatcher threads; the result is printed
    # and every actor system terminated, so leave without joining them
    os._exit(code)
