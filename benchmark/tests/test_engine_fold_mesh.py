"""Configuration ``mesh4-10m``: the cell by name, its rehearsal on four
virtual CPU devices with every per-layer metric a CPU can read, its
control, a run whose timed path is broken underneath, a program whose
sharded wake carries no counters (the parent of the PR that added the
cell) refused at once, and the new readers on what they read."""

import json
import os
import subprocess
import sys
import time
import types

import pytest

from conftest import BENCH_DIR, ROOT

from harness import cell as cells
from harness import mesh_trace
from roofline_gather import gather_bytes
from test_cells import BENCH

CELL = "mesh4-10m.flush-20k"
CONTROL_CELL = "engine-fold-10m.flush-20k"
NEW = ("kernel_ms.mesh4", "gather_ms.mesh4", "gather_ici_pct.mesh4", "shard_steps_max_pct.mesh4")
#: what only a chip's trace can give
DEVICE_ONLY = {"kernel_ms.mesh4", "gather_ms.mesh4", "gather_ici_pct.mesh4",
               "device_busy_ms.engine", "device_idle_pct.engine"}
#: ``run.py`` counts the devices before it loads a driver
FOUR_DEVICES = dict(os.environ, JAX_PLATFORMS="cpu",
                    XLA_FLAGS="--xla_force_host_platform_device_count=4")

#: a whole run in a process of its own, past the look for a chip, with the
#: driver prepared by the code in PREPARE's place; a window of one second: a
#: wake that repairs nothing is quick, and 8,192 actors hold releases for 23
RUN_HERE = """
import sys, types
sys.path[:0] = [{root!r}, {bench!r}]
import run as bench_run
from harness import cell as cells
real_load = cells.load_driver
def load(name):
    module = real_load(name)
    if name == "engine_fold_mesh":
        prepare(module)
    return module
{prepare}
cells.load_driver = load
args = types.SimpleNamespace(workload={cell!r}, seed=21, seconds=1.0, trace=0, rehearse=True,
                             control=False)
sys.exit(bench_run.run(args))
"""


def run_cell(*args):
    proc = subprocess.run([sys.executable, os.path.join(BENCH_DIR, "run.py"), *args], cwd=ROOT,
                          capture_output=True, text=True, timeout=600, env=FOUR_DEVICES)
    return proc, proc.stdout.strip().splitlines()


def run_here(prepare):
    code = RUN_HERE.format(root=ROOT, bench=BENCH_DIR, cell=CELL, prepare=prepare)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                          timeout=600, env=FOUR_DEVICES)
    return proc, proc.stdout.strip().splitlines()


def test_the_cell_loads_by_name_with_its_readers():
    cell = cells.load_cell(CELL)
    control = cells.load_cell(CONTROL_CELL)
    assert cell.chips == 4 and cell.config["driver"] == "engine_fold_mesh"
    assert cell.config["reduced"] == ["chips"]
    assert (cell.config["chips_published"], cell.config["chips_held"]) == (8, 4)
    # the graph, its seed and the traffic are the one-chip control's, unedited
    assert cell.config["graph"] == control.config["graph"]
    assert cell.config["graph"]["actors"] == 10_000_000
    assert cell.config["graph_seed"] == control.config["graph_seed"]
    assert cell.traffic == control.traffic
    uigc = dict(control.config["uigc"], **{"uigc.crgc.shadow-graph": "mesh-decremental",
                                            "uigc.crgc.mesh-devices": 4})
    del uigc["uigc.crgc.pipelined"]  # no key of the program's since PR 48
    assert cell.config["uigc"] == uigc
    assert set(control.config["guarantees"][:3]) <= set(cell.config["guarantees"])
    assert any("same sweep" in g for g in cell.config["guarantees"])
    assert [m.name for m in cell.end_to_end] == ["collected_per_s", "setup_s"]
    names = [m.name for m in cell.per_layer]
    assert set(NEW) <= set(names) and "kernel_ms.engine" not in names  # it sums the planes
    assert {n for n in names if n not in NEW} < {m.name for m in control.per_layer}
    assert cells.load_cell(CELL, rehearse=True).config["graph"]["actors"] == 8192
    entry = [c for c in BENCH["configs"] if c["name"] == "mesh4-10m"][0]
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cell.config["reduced"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["per_layer"]) <= 128


def test_rehearsal_reports_every_per_layer_metric_a_cpu_can_read_and_the_control_fails():
    proc, lines = run_cell("--workload", CELL, "--seed", "4900000101", "--seconds", "3",
                           "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0 and result["device"]["count"] == 4
    named = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    got = result["rehearsed_metrics"]
    assert set(got) == named - DEVICE_ONLY
    assert got["compiles_in_window.engine"]["value"] == 0
    assert got["closure_bailed.engine"]["value"] == 1  # the live set is one component
    assert got["kill_uids.engine"]["value"] == 16  # last_reference_releases_per_wake
    # 8,192 actors in 4 shards of 4,096 padded slots: the live half in the first
    assert 100 <= got["shard_steps_max_pct.mesh4"]["value"] <= 400
    text = "\n".join(lines)
    assert "backend=MeshShadowGraph" in text and "engine_fold_mesh: 4 devices" in text
    for check in ("mesh_devices_differing_from_the_cells_chips", "layout_packs_inside_the_window",
                  "window_wakes_without_counters_from_every_shard",
                  "verdict_words_laid_end_to_end_differing_from_the_whole_verdict",
                  "last_verdict_slots_differing_from_the_uids_delivered", "layout_anomalies"):
        assert f"check {check}: 0 (limit 0) ok" in text, check

    proc, lines = run_cell("--workload", CELL, "--seed", "4900000102", "--seconds", "3",
                           "--trace", "0", "--rehearse", "--control")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["control"] is True and result["correct"] is False
    assert set(result["rehearsed_metrics"]) == {"collected_per_s", "setup_s"}


def test_without_four_devices_there_is_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(FOUR_DEVICES, XLA_FLAGS="--xla_force_host_platform_device_count=2"))
    assert proc.returncode != 0 and "asks for 4 chip(s)" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_a_broken_timed_path_is_not_correct():
    """From the window's first moment on the sharded wake is handed the
    suspects of nothing: its repairs start from no deleted reference."""
    proc, lines = run_here('''
def prepare(module):
    window = module.Driver.window
    def broken_window(self, seconds):
        graph = self.graph
        stage = graph._stage_wake
        def stage_without_suspects():
            graph._pending_del_dst.clear()
            return stage()
        graph._stage_wake = stage_without_suspects
        return window(self, seconds)
    module.Driver.window = broken_window
''')
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False and result["failed"] == 0


def test_a_program_whose_sharded_wake_has_no_counters_is_refused_at_once():
    t0 = time.perf_counter()
    proc, lines = run_here('''
from uigc_tpu.engines.crgc import mesh
del mesh.MeshShadowGraph.wake_stats
def prepare(module):
    pass
''')
    assert proc.returncode not in (0, None) and time.perf_counter() - t0 < 60.0
    assert "carries no counters" in proc.stderr
    assert "set-up generate" not in proc.stdout, "the graph was built first"
    assert not any(line.startswith("{") for line in lines)


def test_gather_bytes_are_a_function_of_shapes_and_the_programs_counter():
    assert gather_bytes(1 << 24, 4, 1) == 1_572_864  # 3/4 of 2 MiB
    assert gather_bytes(1 << 24, 4, 18) == 18 * 1_572_864
    assert gather_bytes(1 << 24, 1, 18) == 0  # one chip receives nothing
    assert gather_bytes(4096, 2, 3) == 3 * 256


def test_a_scope_is_told_from_an_operation_of_the_same_name():
    op = types.SimpleNamespace
    inside = mesh_trace.inside_scope
    assert inside(op(scope="jit(wake_fn)/uigc.wake/repair/while/body/gather/all_gather"), "gather")
    assert inside(op(scope="jit(wake_fn)/uigc.wake/closure/gather/concatenate"), "gather")
    # a lookup in a table is a ``gather`` too: the path's last part is the operation
    assert not inside(op(scope="jit(wake_fn)/uigc.wake/repair/while/body/push/gather"), "gather")
    assert not inside(op(scope="jit(other)/gather/all_gather"), "gather")
    assert inside(op(scope="jit(wake_fn)/uigc.wake/repair/agree/psum"), "agree")


def test_shard_readers_take_the_slowest_shard_and_nothing_from_one_chip(monkeypatch):
    read = cells.reader_of("layers", "shard_steps_max_pct.mesh4")
    wakes = [{"kernel_steps": [30, 10, 0, 0], "gathers": 18},
             {"kernel_steps": [10, 10, 10, 10], "gathers": 18},
             {"kernel_steps": [40, 0, 0, 0], "gathers": 18}]
    monkeypatch.setattr(mesh_trace, "window_wake_stats", lambda obs: wakes)
    assert read(None) == 300.0  # the median of 300, 100, 400
    monkeypatch.setattr(mesh_trace, "window_wake_stats", lambda obs: [{"kernel_steps": 50}])
    assert read(None) is None  # one chip's counters
    monkeypatch.setattr(mesh_trace, "window_wake_stats", lambda obs: None)
    assert read(None) is None

    # the kernel's time: the fullest plane's, not the planes' sum
    call = "%c = f32[8,128] custom-call(%a), custom_call_target='tpu_custom_call'"
    trace = types.SimpleNamespace(
        device_events=[[(call, 0.0, 0.030), ("%f = fusion(%a)", 0.03, 0.04), (call, 0.05, 0.06)],
                       [(call, 0.0, 0.010)], [], []],
        spans_inside=lambda name: 2)
    obs = types.SimpleNamespace(trace=trace)
    assert cells.reader_of("layers", "kernel_ms.mesh4")(obs) == pytest.approx(20.0)
    assert cells.reader_of("layers", "kernel_ms.mesh4")(types.SimpleNamespace(trace=None)) is None
