#!/usr/bin/env python
"""telemetry-dump: render uigc telemetry as Prometheus text or JSON.

Three sources, one output pipeline (build a metrics registry, render):

- ``--from-jsonl PATH``  replay a persisted JSONL event log
  (``uigc.telemetry.jsonl-path``) through the same event->metrics
  bridge a live system uses, so an offline dump and a live scrape of
  the same run agree;
- ``--demo``             run a tiny in-process workload with telemetry
  attached (spawn/churn/release under a fast collector) and dump what
  it produced — the zero-to-metrics smoke;
- ``--snapshot PATH``    pretty-print a recorder snapshot JSON file
  (``events.recorder.snapshot()`` saved by your driver) as-is.

Output: ``--format prom`` (default; Prometheus text exposition) or
``--format json`` (the registry snapshot).  One document to stdout.

``--series NAME`` switches to the telemetry time plane: render one
stored series (every labelset fan-out) as an ASCII sparkline + stats,
from a live ``/timeseries`` endpoint (``--url``) or a JSONL replay
(``--from-jsonl``) — the renderers are shared with ``tools/uigc_top.py``.

``--device`` renders the device-plane observatory
(``uigc.telemetry.device``): from a live ``/device`` endpoint
(``--url``) or by replaying the event-fed planes (compile cache, host
transfers, donation audit) out of a JSONL sink — the renderers are
``tools/device_report.py``'s.

``--wakes PATH`` renders the wake profiler's records
(``uigc.telemetry.wake-profile``): a ``WakeProfiler.dump()`` document, or
the JSON-lines file of a ``profile.record_sink`` (a record a finished
wake, a record a stall), as per-field medians, the collector's phases on
and off the CPU with the sweep's wall split into who ran, and the stalls
with their reading.  With ``--bench ARGS...`` it first runs
``benchmark/run.py ARGS...`` in this process under such a sink writing
PATH (each line after its wake has ended, outside every phase); with
``--stacks`` also under ``WakeProfiler.dump_stalls_to(PATH.stacks)``,
which takes the run's own time limit off (one ``faulthandler`` timer a
process) and can, rarely, crash the run inside a dump: for hunting a
stall, not for a run whose numbers are kept.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _registry(node: str):
    from uigc_tpu.telemetry.metrics import EventMetricsBridge, MetricsRegistry

    registry = MetricsRegistry(const_labels={"node": node})
    return registry, EventMetricsBridge(registry)


def dump_from_jsonl(path: str, fmt: str) -> int:
    from uigc_tpu.telemetry.exporter import prometheus_text, replay_jsonl

    registry, bridge = _registry(node=f"replay:{Path(path).name}")
    n = 0
    for name, fields in replay_jsonl(path):
        bridge(name, fields)
        n += 1
    if n == 0:
        print(f"telemetry-dump: no events in {path!r}", file=sys.stderr)
        return 1
    if fmt == "json":
        print(json.dumps(registry.snapshot(), indent=2, sort_keys=True, default=repr))
    else:
        sys.stdout.write(prometheus_text(registry))
    return 0


def dump_demo(fmt: str) -> int:
    from uigc_tpu import AbstractBehavior, ActorTestKit, Behaviors, NoRefs
    from uigc_tpu.telemetry.exporter import prometheus_text

    class Ping(NoRefs):
        pass

    class Worker(AbstractBehavior):
        def on_message(self, msg):
            return self

    class Root(AbstractBehavior):
        def __init__(self, context):
            super().__init__(context)
            self.workers = [
                context.spawn(Behaviors.setup(Worker), f"w{i}") for i in range(8)
            ]

        def on_message(self, msg):
            ctx = self.context
            if isinstance(msg, Ping) and self.workers:
                for worker in self.workers:
                    worker.tell(Ping(), ctx)
            elif self.workers:
                ctx.release(*self.workers)
                self.workers = []
            return self

    kit = ActorTestKit(
        config={
            "uigc.crgc.wakeup-interval": 10,
            "uigc.telemetry.metrics": True,
            "uigc.telemetry.wake-profile": True,
        },
        name="telemetry-demo",
    )
    try:
        root = kit.spawn(Behaviors.setup_root(Root), "root")
        for _ in range(50):
            root.tell(Ping())
        time.sleep(0.3)
        root.tell(object())  # release branch
        time.sleep(0.5)
        telemetry = kit.system.telemetry
        if fmt == "json":
            doc = {
                "metrics": telemetry.registry.snapshot(),
                "wake_profile": telemetry.profiler.to_json(),
            }
            print(json.dumps(doc, indent=2, sort_keys=True, default=repr))
        else:
            sys.stdout.write(prometheus_text(telemetry.registry))
    finally:
        kit.shutdown()
    return 0


def dump_snapshot(path: str, fmt: str) -> int:
    with open(path) as fh:
        snap = json.load(fh)
    if fmt == "json":
        print(json.dumps(snap, indent=2, sort_keys=True))
        return 0
    # Render a recorder snapshot as gauges/counters: counts are
    # monotone (counter-like), sums and duration stats become gauges.
    lines = []
    for name, count in sorted(snap.get("counts", {}).items()):
        metric = "uigc_event_total{event=\"%s\"}" % name
        lines.append(f"{metric} {count}")
    for name, value in sorted(snap.get("sums", {}).items()):
        lines.append('uigc_event_sum{field="%s"} %s' % (name, value))
    for name, stat in sorted(snap.get("durations", {}).items()):
        for key in ("n", "total_s", "max_s"):
            lines.append(
                'uigc_event_duration_%s{event="%s"} %s' % (key, name, stat[key])
            )
    sys.stdout.write("\n".join(lines) + "\n")
    return 0


def dump_inspect(path, actor, fmt) -> int:
    """Pretty-print a liveness-inspector snapshot (and optionally one
    why-live retaining path): from a dumped JSON file when ``path`` is
    given, else from a live in-process demo system — the rendering is
    shared with tools/graph_inspect.py."""
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import graph_inspect

    from uigc_tpu.telemetry.inspect import why_live

    if path:
        snap = graph_inspect.load_snapshot(path)
        result = why_live(snap, actor) if actor else None
    else:
        demo = graph_inspect.DemoSystem()
        try:
            snap = demo.inspector.snapshot()
            result = demo.inspector.why_live(actor) if actor else None
        finally:
            demo.shutdown()
    if fmt == "json":
        doc = {"snapshot": snap}
        if result is not None:
            doc["why_live"] = result
        print(json.dumps(doc, indent=2, sort_keys=True, default=repr))
    else:
        print(graph_inspect.render_snapshot(snap))
        if result is not None:
            print(graph_inspect.render_why_live(result))
    return 0


def dump_series(name, url, jsonl, fmt) -> int:
    """Render one stored time-plane series (every labelset fan-out) as
    an ASCII sparkline + stats, from a live ``/timeseries`` endpoint or
    a JSONL replay — the renderers are tools/uigc_top.py's."""
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import uigc_top

    if url:
        try:
            tsdoc, _alerts, _metrics = uigc_top.fetch_live(
                url.rstrip("/"), window=1e9
            )
        except Exception as exc:
            print(f"telemetry-dump: {exc}", file=sys.stderr)
            return 1
    else:
        try:
            tsdoc, _alerts, _metrics = uigc_top.replay_model(jsonl)
        except (FileNotFoundError, OSError) as exc:
            print(f"telemetry-dump: {exc}", file=sys.stderr)
            return 1
    matching = [s for s in tsdoc.get("series", []) if s.get("name") == name]
    if not matching:
        known = sorted({s.get("name") for s in tsdoc.get("series", [])})
        print(
            f"telemetry-dump: no series {name!r}; known: {', '.join(known)}",
            file=sys.stderr,
        )
        return 1
    if fmt == "json":
        print(json.dumps(
            {"name": name, "series": matching},
            indent=2, sort_keys=True, default=repr,
        ))
        return 0
    mode = "rate" if name.endswith("_total") else "mean"
    print(f"{name}  ({len(matching)} labelset(s), mode={mode})")
    for series in matching:
        labels = series.get("labels") or {}
        label = (
            ",".join(f"{k}={v}" for k, v in sorted(labels.items())) or "(all)"
        )
        points = uigc_top.series_points(series, mode)
        print("  " + uigc_top.render_series(label[:16], points, width=48))
    return 0


def dump_device(url, jsonl, fmt) -> int:
    """Render the device observatory: live ``/device`` or the event-fed
    planes replayed from a JSONL sink (tools/device_report.py
    renderers)."""
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import device_report
    import uigc_top

    if url:
        try:
            doc = device_report.fetch_doc(url.rstrip("/"))
        except Exception as exc:
            print(
                f"telemetry-dump: no /device at {url} "
                f"(uigc.telemetry.device off?): {exc}",
                file=sys.stderr,
            )
            return 1
    else:
        doc = uigc_top.replay_device(jsonl)
        if doc is None:
            print(
                f"telemetry-dump: no replayable events in {jsonl!r}",
                file=sys.stderr,
            )
            return 1
    if fmt == "json":
        print(json.dumps(doc, indent=2, sort_keys=True, default=repr))
        return 0
    print(
        device_report.render_device_doc(
            doc, device_report.committed_device_figures()
        )
    )
    return 0


# ------------------------------------------------------------------- #
# --wakes: the wake profiler's records
# ------------------------------------------------------------------- #


def load_wakes(path: str):
    """``(wake records, stall records)`` of a ``WakeProfiler.dump()``
    document or of a sink's JSON-lines file."""
    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rt") as fh:
            text = fh.read()
    else:
        text = Path(path).read_text()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict) and "recent" in doc:
        return doc["recent"], doc.get("stalls", [])
    records = [json.loads(line) for line in text.splitlines() if line.strip()]
    return ([r for r in records if "wall_s" in r], [r for r in records if "late_s" in r])


def _median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def stall_reading(stall) -> str:
    """Whose a stall was, from the CPU time across it: the process had
    none (nobody ran: the host's), or somebody ran all the while (the
    program's: a thread held the GIL), named by class where one had it."""
    late, ran = stall["late_s"], stall["process_cpu_s"]
    if ran < 0.2 * late:
        return "the host's: nobody ran"
    if ran < 0.8 * late:
        return "mixed"
    for name in ("collector", "workers", "timer"):
        cpu = stall.get(name + "_cpu_s")
        if cpu is not None and cpu > 0.5 * late:
            return f"the program's: the {name} ran"
    return "the program's: another thread ran (the driver's, XLA's)"


def _mean(values):
    values = [v for v in values if v is not None]
    return statistics.fmean(values) if values else None


def _sweep_split(swept):
    """Means a wake over ``swept``: the sweep's wall, the collector's CPU
    in it, the workers', what is left, and CPython's collector."""
    ms = 1e3
    wall = _mean(w["phases"]["sweep"] * ms for w in swept)
    cpu = _mean(w["phases_cpu"]["sweep"] * ms for w in swept)
    workers = _mean(
        None if w.get("workers_cpu_sweep_s") is None else w["workers_cpu_sweep_s"] * ms
        for w in swept)
    return {
        "wakes": len(swept),
        "sweep_ms": wall,
        "sweep_cpu_ms": cpu,
        "sweep_workers_cpu_ms": workers,
        "sweep_unrun_ms": None if workers is None else wall - cpu - workers,
        "gc_in_sweep_ms": _mean(w["gc_sweep_s"] * ms for w in swept),
    }


def summarize_wakes(wakes, stalls):
    """What ``--wakes`` renders, as a document.  ``fields``: the median
    of every numeric field over all wakes and over those that called the
    device (the benchmark's readers' wakes).  The CPU clocks are read as
    MEANS a wake (sums over the window): where a host's thread clocks
    tick coarsely (10 ms under gVisor: ``cpu_tick_ms``) one wake's value
    is a multiple of the tick and only the sum tells.  ``phases``: per
    phase, over the wakes that entered it, the median and the mean of its
    wall, the mean of its thread CPU and the share of its wall off the
    CPU.  ``sweep``: over the wakes that swept, the sweep's wall split
    into the collector's CPU, the workers' CPU and the remainder nobody
    of the runtime ran (under 0 where more than one thread ran at a
    time), with CPython's collector's part; ``sweep_typical``: the same
    without the wakes a FULL collection paused.  The names are those
    PERF.md reserves for the readers."""
    called = [w for w in wakes if w.get("device_s")]
    names = sorted({
        k for w in wakes for k, v in w.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool) and k not in ("t", "wake")
    })
    ms = 1e3
    phases = {}
    for name in sorted({name for w in wakes for name in w.get("phases_cpu", {})}):
        entered = [w for w in wakes if w["phases"].get(name)]
        if entered:
            wall = sum(w["phases"][name] for w in entered)
            cpu = sum(w["phases_cpu"][name] for w in entered)
            phases[name] = {
                "wakes": len(entered),
                "wall_median_ms": _median(w["phases"][name] * ms for w in entered),
                "wall_ms": wall * ms / len(entered),
                "cpu_ms": cpu * ms / len(entered),
                "off_cpu_pct": 100.0 * (wall - cpu) / wall,
            }
    swept = [w for w in wakes if w.get("phases_cpu", {}).get("sweep") is not None
             and w["phases"]["sweep"] and w.get("freed")]
    typical = [w for w in swept if not w["gc_full"]]
    # an empty timer wake is microseconds of brackets: the device wakes' where there are any
    clocked = [w for w in (called or wakes) if "cpu_s" in w and w["wall_s"] > 0]
    wall = sum(w["wall_s"] for w in clocked)
    ticks = [w["cpu_s"] for w in clocked if w["cpu_s"] > 0]
    return {
        "wakes": len(wakes),
        "called_the_device": len(called),
        "cpu_tick_ms": min(ticks) * ms if ticks else None,
        "fields": {
            name: {"all": _median(w.get(name) for w in wakes),
                   "device": _median(w.get(name) for w in called)}
            for name in names
        },
        "phases": phases,
        "sweep": _sweep_split(swept) if swept else None,
        "sweep_typical": _sweep_split(typical) if typical and len(typical) != len(swept) else None,
        "wake_off_cpu_pct": (
            100.0 * (wall - sum(w["cpu_s"] for w in clocked)) / wall if clocked else None),
        "gc_in_wake_ms": _mean(w["gc_s"] * ms for w in clocked),
        "stalls_in_window": len(stalls),
        "stalls": [dict(stall, reading=stall_reading(stall)) for stall in stalls],
    }


def render_wakes(summary) -> str:
    def num(value, width=10):
        return f"{'-':>{width}}" if value is None else f"{value:>{width}.4g}"

    out = [f"wakes {summary['wakes']}  called the device {summary['called_the_device']}  "
           f"stalls {summary['stalls_in_window']}"]
    out.append(f"\n{'field (median)':<26}{'all wakes':>12}{'device wakes':>14}")
    for name, cols in summary["fields"].items():
        out.append(f"{name:<26}{num(cols['all'], 12)}{num(cols['device'], 14)}")
    tick = summary["cpu_tick_ms"]
    out.append(
        "\non and off the CPU, means a wake (the smallest CPU reading of a wake is "
        f"{num(tick, 0)} ms: where that is a clock's tick, only sums tell)")
    out.append(f"{'phase':<12}{'wakes':>7}{'wall median':>13}{'wall ms':>11}{'cpu ms':>11}"
               f"{'off-CPU %':>11}")
    for name, row in summary["phases"].items():
        out.append(f"{name:<12}{row['wakes']:>7}{num(row['wall_median_ms'], 13)}"
                   f"{num(row['wall_ms'], 11)}{num(row['cpu_ms'], 11)}"
                   f"{num(row['off_cpu_pct'], 11)}")
    out.append(f"wake_off_cpu_pct {num(summary['wake_off_cpu_pct'], 8)}   "
               f"gc_in_wake_ms {num(summary['gc_in_wake_ms'], 8)}")
    for key, which in (("sweep", "that swept"),
                       ("sweep_typical", "that swept and no full collection paused")):
        sweep = summary[key]
        if sweep is not None:
            out.append(
                f"\nthe sweep of the {sweep['wakes']} wakes {which}, ms a wake (mean): wall "
                f"{num(sweep['sweep_ms'], 0)} = collector's CPU {num(sweep['sweep_cpu_ms'], 0)} "
                f"+ workers' CPU {num(sweep['sweep_workers_cpu_ms'], 0)} + nobody of the "
                f"runtime {num(sweep['sweep_unrun_ms'], 0)} (CPython's collector "
                f"{num(sweep['gc_in_sweep_ms'], 0)} of the wall)")
    out.append("\nstalls:" if summary["stalls"] else "\nstalls: none")
    for stall in summary["stalls"]:
        where = "" if stall.get("wake") is None else f" in wake {stall['wake']} ({stall.get('phase')})"
        dump = "" if stall.get("dump_offset") is None else f", stacks at byte {stall['dump_offset']}"
        out.append(
            f"  at {stall['at']:.3f}: late {stall['late_s']:.3f} s, process CPU "
            f"{stall['process_cpu_s']:.3f} (collector {num(stall.get('collector_cpu_s'), 0)}, "
            f"workers {num(stall.get('workers_cpu_s'), 0)}, timer "
            f"{num(stall.get('timer_cpu_s'), 0)}){where}{dump}: {stall['reading']}")
    return "\n".join(out)


def run_benchmark_under_sink(path: str, bench_argv, stacks: bool) -> int:
    """``benchmark/run.py``'s ``main(bench_argv)`` in this process, every
    wake's record and every stall's written to ``path`` as a JSON line by
    ``profile.record_sink`` (the benchmark's files are run, not edited).
    Returns the run's exit code."""
    import runpy

    from uigc_tpu.telemetry import profile

    bench = Path(__file__).resolve().parent.parent / "benchmark"
    sys.path.insert(0, str(bench))
    # not as ``__main__``: that road ends in ``os._exit``
    run = runpy.run_path(str(bench / "run.py"), run_name="benchmark_run")
    lock = threading.Lock()  # the collector's thread and the watchdog's
    with open(path, "w") as fh:
        def sink(record):
            line = json.dumps(record, default=repr)
            with lock:
                fh.write(line + "\n")

        profile.record_sink = sink
        if stacks:
            profile.WakeProfiler.dump_stalls_to(path + ".stacks")
            print("telemetry-dump: --stacks re-arms faulthandler's one timer ten times a "
                  "second: benchmark/run.py's own time limit is OFF for this run, and a "
                  "dump taken while the GIL's holder runs Python can crash it (a hunt's "
                  "option: PROFILING.md)", file=sys.stderr)
        try:
            return run["main"](list(bench_argv))
        finally:
            profile.record_sink = None
            if stacks:
                profile.WakeProfiler.dump_stalls_to(None)


def dump_wakes(path: str, fmt: str, bench_argv=None, stacks: bool = False,
               from_wake: int = 0, to_wake=None) -> int:
    code = 0
    if bench_argv is not None:
        code = run_benchmark_under_sink(path, bench_argv, stacks)
    try:
        wakes, stalls = load_wakes(path)
    except (OSError, ValueError) as exc:
        print(f"telemetry-dump: {exc}", file=sys.stderr)
        return 1
    wakes = [w for w in wakes
             if w["wake"] >= from_wake and (to_wake is None or w["wake"] < to_wake)]
    if not wakes and not stalls:
        print(f"telemetry-dump: no wake records in {path!r} "
              "(uigc.telemetry.wake-profile off?)", file=sys.stderr)
        return code or 1
    summary = summarize_wakes(wakes, stalls)
    if fmt == "json":
        print(json.dumps(summary, indent=2, sort_keys=True, default=repr))
    else:
        print(render_wakes(summary))
    return code


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="telemetry-dump", description=__doc__.splitlines()[0]
    )
    parser.add_argument(
        "--series",
        metavar="NAME",
        help="render one time-plane series (sparkline + stats) from "
        "--url or --from-jsonl (tools/uigc_top.py renderers)",
    )
    parser.add_argument(
        "--device",
        action="store_true",
        help="render the device-plane observatory from --url (/device) "
        "or --from-jsonl (tools/device_report.py renderers)",
    )
    parser.add_argument(
        "--url",
        metavar="URL",
        help="live metrics-HTTP base URL for --series",
    )
    parser.add_argument(
        "--wakes",
        metavar="PATH",
        help="render the wake profiler's records: a WakeProfiler.dump() "
        "document or a record sink's JSON-lines file",
    )
    parser.add_argument(
        "--from-wake",
        type=int,
        default=0,
        metavar="N",
        help="with --wakes: leave out the wakes before ordinal N (a run's "
        "first wakes load or compile their programs, and sums feel them)",
    )
    parser.add_argument(
        "--to-wake",
        type=int,
        default=None,
        metavar="M",
        help="with --wakes: leave out the wakes from ordinal M on (what "
        "follows a benchmark's window: its comparison, its shutdown)",
    )
    parser.add_argument(
        "--stacks",
        action="store_true",
        help="with --wakes --bench: dump every thread's stack while the "
        "process stands still (PATH.stacks); takes the run's own time "
        "limit off",
    )
    parser.add_argument(
        "--bench",
        nargs=argparse.REMAINDER,
        metavar="ARGS",
        help="with --wakes: first run benchmark/run.py ARGS... under a "
        "record sink writing PATH (the rest of the command line is "
        "run.py's)",
    )
    source = parser.add_mutually_exclusive_group()
    source.add_argument("--from-jsonl", metavar="PATH", help="replay a JSONL event log")
    source.add_argument(
        "--demo", action="store_true", help="run a tiny workload and dump its metrics"
    )
    source.add_argument(
        "--snapshot", metavar="PATH", help="render a saved recorder snapshot JSON"
    )
    source.add_argument(
        "--inspect",
        nargs="?",
        const="",
        metavar="SNAPJSON",
        default=None,
        help="pretty-print a liveness snapshot (from SNAPJSON when "
        "given, else from a live demo system); combine with --actor "
        "for a why-live path (tools/graph_inspect.py)",
    )
    parser.add_argument(
        "--actor", metavar="NAME", help="actor to explain with --inspect"
    )
    parser.add_argument(
        "--format",
        choices=("prom", "json"),
        default="prom",
        help="output format (default: prom)",
    )
    args = parser.parse_args(argv)
    if args.wakes:
        code = dump_wakes(args.wakes, args.format, args.bench, args.stacks, args.from_wake,
                          args.to_wake)
        if args.bench is not None:
            # as benchmark/run.py leaves: the served runtime keeps threads
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(code)
        return code
    if args.bench is not None or args.stacks:
        parser.error("--bench and --stacks go with --wakes PATH")
    if args.device:
        if not args.url and not args.from_jsonl:
            parser.error("--device needs --url or --from-jsonl")
        return dump_device(args.url, args.from_jsonl, args.format)
    if args.series:
        if not args.url and not args.from_jsonl:
            parser.error("--series needs --url or --from-jsonl")
        return dump_series(args.series, args.url, args.from_jsonl, args.format)
    if args.inspect is not None:
        return dump_inspect(args.inspect, args.actor, args.format)
    if args.from_jsonl:
        return dump_from_jsonl(args.from_jsonl, args.format)
    if args.snapshot:
        return dump_snapshot(args.snapshot, args.format)
    if args.demo:
        return dump_demo(args.format)
    parser.error(
        "one of --from-jsonl / --demo / --snapshot / --inspect is required"
    )
    return 2


if __name__ == "__main__":
    sys.exit(main())
