"""Configuration ``mac-rings-100k``: the cell by name, its rehearsal end to
end on the CPU with the per-layer metrics a CPU can read, its control, runs
whose timed path is broken underneath (the detector finds nothing once the
window starts; the detector kills a set one of whose members has unblocked
since it was asked), and the limits the contract sets on what this cell
added.  Rehearsal sizes; no number here is a measurement."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

CELL = "mac-rings-100k.ring-sessions"
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BENCH = json.load(fh)
#: what only a chip's trace can give
DEVICE_ONLY = {"device_busy_ms.served", "device_idle_pct.served", "idle_in_wake_pct.served"}
#: read over the wakes that both traced and freed: with the rehearsal's few
#: sessions in flight the tick that kills a set often drains nothing but its
#: ACKs, and so does not trace
NOT_EVERY_RUN = {"stop_cascade_ms.served", "sweep_edge_slots.served"}

#: a whole run in a process of its own, past the look for a chip, with the
#: driver prepared by the code in PREPARE's place
RUN_HERE = """
import sys, types
sys.path[:0] = [{root!r}, {bench!r}]
import run as bench_run
from harness import cell as cells
real_load = cells.load_driver
def load(name):
    module = real_load(name)
    if name == "served_mac":
        prepare(module)
    return module
{prepare}
cells.load_driver = load
args = types.SimpleNamespace(workload={cell!r}, seed=52, seconds={seconds}, trace=0,
                             rehearse=True, control=False)
sys.exit(bench_run.run(args))
"""

#: the detector asks nobody from the window on: nothing of a session stops
NO_DETECTION = """
def prepare(module):
    window = module.Driver.window
    def broken(self, seconds):
        self.detector._probe = lambda garbage_slots, touched: 0
        return window(self, seconds)
    module.Driver.window = broken
"""

#: the message goes round a session's ring for about a second, so most
#: ticks find the ring all blocked with the message between two members
LONG_HOPS = """
def prepare(module):
    window = module.Driver.window
    def long_hops(self, seconds):
        self.ctx.traffic["use_hops"] = 64 * 300
        return window(self, seconds)
    module.Driver.window = long_hops
"""

#: and a third of the way into the window the members of the session
#: started last are killed with its message on its way round: what a
#: detector does that takes a ring in use for garbage
REAPED_IN_USE = LONG_HOPS.replace("return window(self, seconds)", """
        def reap():
            import time
            from uigc_tpu.engines.mac.engine import KillMsg
            while self.in_window:
                for s in [s for s in self.sessions if not s.used and s.left == s.size]:
                    mine = "/s%dm" % s.sid
                    cells = [c for c in list(self.detector.graph.cells)
                             if c is not None and mine in c.path]
                    if len(cells) == s.size and not s.used:
                        for cell in cells:
                            cell.tell(KillMsg)
                        return
                time.sleep(0.01)
        import threading
        threading.Timer(seconds / 3, reap).start()
        return window(self, seconds)""")


def run_cell(*args):
    proc = subprocess.run([sys.executable, os.path.join(BENCH_DIR, "run.py"), *args], cwd=ROOT,
                          capture_output=True, text=True, timeout=600, env=ENV)
    return proc, proc.stdout.strip().splitlines()


def test_the_cell_loads_by_name_with_its_readers():
    from harness import cell as cells

    cell = cells.load_cell(CELL)
    served = cells.load_cell("tree-100k.sessions")
    assert cell.chips == 1 and cell.config["driver"] == "served_mac"
    assert cell.config["reduced"] == ["resident_actors"] and cell.config["architecture"] is None
    res = cell.config["resident"]
    assert res["rings"] * res["ring_size"] + res["supervisors"] == 100_000
    assert cell.config["uigc"] == {
        "uigc.engine": "mac", "uigc.mac.cycle-detection": True,
        "uigc.mac.collect-cycles": True, "uigc.mac.wakeup-interval": 50,
        "uigc.mac.shadow-graph": "decremental", "uigc.analysis.sanitizer": False,
    }
    assert len(cell.config["guarantees"]) == 5 and cell.config["assumed"]
    # the traffic is sessions.json's, rings for trees
    for key in ("sessions_in_flight", "session_actors", "pings_per_s", "warmup_sessions",
                "warmup_s", "grace_s", "probe_residents", "trace_seconds"):
        assert cell.traffic[key] == served.traffic[key], key
    assert cell.traffic["use_hops"] == 64 and "session_fanout" not in cell.traffic
    # the served metrics, all three, and every ``.served`` per-layer entry
    # whose span or counter the MAC road records: all but the age of the
    # oldest flush (MAC has no flush)
    assert [m.name for m in cell.end_to_end] == [m.name for m in served.end_to_end]
    assert {"stop_p50_ms", "stop_p95_ms", "stopped_per_s"} < {m.name for m in cell.end_to_end}
    names = {m.name for m in cell.per_layer}
    assert {m.name for m in served.per_layer if m.name.endswith(".served")} - names == {
        "ingest_wait_ms.served"}
    assert all(n.endswith(".served") for n in names) and len(names) == 19
    rehearsed = cells.load_cell(CELL, rehearse=True)
    assert rehearsed.config["resident"]["rings"] == 24 and rehearsed.config["ping_pairs"] == 4
    entry = [c for c in BENCH["configs"] if c["name"] == "mac-rings-100k"][0]
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cell.config["reduced"]


def test_the_contracts_limits_hold_and_nothing_of_the_scc_road_is_left():
    from uigc_tpu import config

    assert len(BENCH["per_layer"]) == 128 and len(BENCH["workloads"]) == 9
    assert len(BENCH["configs"]) == 8 and BENCH["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(config.DEFAULTS) == 95
    assert "uigc.mac.device-scc-threshold" not in config.DEFAULTS
    assert config.DEFAULTS["uigc.mac.shadow-graph"] == "array"
    assert not os.path.exists(os.path.join(ROOT, "uigc_tpu", "ops", "scc.py"))
    for base, _dirs, files in os.walk(os.path.join(ROOT, "uigc_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as fh:
                    text = fh.read()
                assert "device-scc-threshold" not in text, name
                assert "ops import scc" not in text and "ops.scc" not in text, name
    with open(os.path.join(BENCH_DIR, "reference_mac.py")) as fh:
        reference = fh.read()
    assert "import uigc_tpu" not in reference and "from uigc_tpu" not in reference
    assert "numpy" not in reference


def test_rehearsal_end_to_end_with_the_per_layer_metrics_a_cpu_can_read():
    proc, lines = run_cell("--workload", CELL, "--seed", "5200000101", "--seconds", "3",
                           "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    named = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    got = set(result["rehearsed_metrics"])
    assert named - DEVICE_ONLY - NOT_EVERY_RUN <= got <= named - DEVICE_ONLY
    text = "\n".join(lines)
    assert "compile requests inside 0 " in text
    assert "impl=pallas-interpret" in text and "spans wake:" in text
    # held against the reference with sessions in flight: a token open and
    # a set that is not empty
    mid = [line for line in lines if "served_mac: mid_window:" in line][0]
    assert " 0 in a pending confirmation" not in mid and "detector 0, reference 0; garbage" not in mid
    for check in ("mid_window_audit_of_a_wake_that_asked_nobody",
                  "mid_window_asked_differing_from_reference_mac",
                  "mid_window_garbage_differing_from_reference_mac",
                  "at_rest_asked_differing_from_reference_mac",
                  "at_rest_garbage_differing_from_reference_mac", "sessions_not_stopped",
                  "sessions_with_a_stop_before_the_last_hop",
                  "session_actors_without_exactly_one_poststop", "pings_unanswered",
                  "residents_not_answering_of_100", "resident_poststops", "detector_cell_dead",
                  "no_device_wake"):
        assert f"check {check}: 0 (limit 0) ok" in text, check


def test_the_control_is_not_correct():
    proc, lines = run_cell("--workload", CELL, "--seed", "5200000102", "--seconds", "3",
                           "--rehearse", "--control")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["control"] is True and result["correct"] is False
    text = "\n".join(lines)
    # one resident ring's kept reference went at half time: its 64 members
    # stopped, and the session side is untouched
    assert "check resident_poststops: 64 (limit 0) NOT CORRECT" in text
    assert "check sessions_not_stopped: 0 (limit 0) ok" in text
    assert "check sessions_with_a_stop_before_the_last_hop: 0 (limit 0) ok" in text
    assert "check at_rest_garbage_differing_from_reference_mac: 0 (limit 0) ok" in text


def run_prepared(prepare, seconds):
    code = RUN_HERE.format(root=ROOT, bench=BENCH_DIR, cell=CELL, prepare=prepare, seconds=seconds)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                          timeout=600, env=ENV)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(lines[-1]), "\n".join(lines)


def test_a_detector_that_finds_nothing_in_the_window_is_not_correct():
    result, text = run_prepared(NO_DETECTION, 1.0)
    assert result["correct"] is False and result["failed"] > 0
    assert "check sessions_not_stopped: 0 (limit 0) ok" not in text
    assert "check no_mid_window_audit: 1 (limit 0) NOT CORRECT" in text
    assert "check resident_poststops: 0 (limit 0) ok" in text


@pytest.mark.parametrize("prepare,holds", [(LONG_HOPS, True), (REAPED_IN_USE, False)],
                         ids=["a_message_in_flight_keeps_a_ring", "reaped_in_use"])
def test_a_detector_that_kills_a_ring_with_a_message_in_flight_is_not_correct(prepare, holds):
    result, text = run_prepared(prepare, 3.0)
    assert ("check sessions_with_a_stop_before_the_last_hop: 0 (limit 0) ok" in text) is holds
    assert holds or result["correct"] is False
    # every member still gets exactly one PostStop: only the hop tells
    assert "check session_actors_without_exactly_one_poststop: 0 (limit 0) ok" in text
