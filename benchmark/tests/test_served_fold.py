"""Configuration ``served-10m``: the cell found by name, its rehearsal with
every per-layer metric a CPU can read, its refusal without a chip, its
control, the foreign side's comparison handed a reference with one uid
flipped, a run whose timed path is broken underneath, a program without the
hold (the parent of the PR that added it) refused at once, and each new
reader on a hand-made wake record."""

import json
import sys
import time
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

from harness import cell as cells
from test_cells import BENCH, run_cell

CELL = "served-10m.sessions"
#: what only a chip's trace can give
DEVICE_ONLY = {"kernel_ms.served10m", "closure_ms.served10m", "device_busy_ms.served10m",
               "device_idle_pct.served10m", "idle_in_wake_pct.served10m"}
NAMED = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}


def test_the_cell_loads_by_name():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "served_fold"
    assert cell.config["reduced"] == [] and cell.config["graph"]["actors"] == 10_000_000
    assert cell.config["resident"] == {"actors": 100000, "fanout": 8}
    assert cell.config["uigc"]["uigc.crgc.wakeup-interval"] == 50
    assert cell.traffic == cells.load_cell("tree-100k.sessions").traffic
    assert {m.name for m in cell.end_to_end} == {"stop_p50_ms", "stop_p95_ms", "stopped_per_s",
                                                 "setup_s"}
    assert {m.name for m in cell.per_layer} == NAMED and len(NAMED) == 31
    assert all(name.endswith(".served10m") for name in NAMED)
    small = cells.load_cell(CELL, rehearse=True)
    assert small.config["graph"]["actors"] == 8192 and small.config["resident"]["actors"] == 1500
    assert cells.load_driver("served_fold").Driver.__mro__[1].__module__ == "bench_driver_served"


def test_the_copies_move_what_their_originals_move():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NAMED:
        original = by_name.get(name.replace(".served10m", ".served"))
        if original is not None:
            copy = by_name[name]
            assert {k: copy[k] for k in ("unit", "better", "source", "layer", "moves")} == \
                {k: original[k] for k in ("unit", "better", "source", "layer", "moves")}, name


def test_rehearsal_reports_every_per_layer_metric_a_cpu_can_read():
    proc, lines = run_cell("--workload", CELL, "--seed", "2147483659", "--seconds", "3",
                           "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    got = result["rehearsed_metrics"]
    assert set(got) == NAMED - DEVICE_ONLY
    assert got["actors_foreign.served10m"]["value"] == 4096  # the generator's live half
    assert got["actors_local.served10m"]["value"] >= 1500
    assert got["closure_bailed.served10m"]["value"] == 0
    assert 0 < got["gated_tiles.served10m"]["value"] < 4  # of four supertiles at 16,384 slots
    assert got["compiles_in_window.served10m"]["value"] == 0
    assert got["upload_mb.served10m"]["value"] == pytest.approx(16384 * 9e-6)  # flags + recv_count
    text = "\n".join(lines)
    for phase in ("generate", "resident tree", "pairs and owners", "encode and fold",
                  "first trace after the hold", "warm-up"):
        assert f"set-up {phase}" in text, phase
    assert "freed uids=4096 " in text
    assert "spans wake: n=" in text, "no bench:wake span around the collector's device call"


def test_timed_run_reports_the_end_to_end_metrics():
    proc, lines = run_cell("--workload", CELL, "--seed", "3", "--seconds", "3", "--trace", "0",
                           "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert set(result["rehearsed_metrics"]) == {"stop_p50_ms", "stop_p95_ms", "stopped_per_s",
                                                "setup_s"}


def test_without_a_chip_there_is_no_result():
    proc, lines = run_cell("--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in lines)


def test_the_control_comes_out_not_correct():
    """``served.py``'s control: the resident root releases one of its
    children in mid-window, and the check is not told."""
    proc, lines = run_cell("--workload", CELL, "--seed", "2147483701", "--seconds", "3",
                           "--trace", "0", "--rehearse", "--control")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["control"] is True and result["correct"] is False
    assert "check resident_poststops: 0 " not in "\n".join(lines)


def _run_here(monkeypatch, prepare):
    """A whole run in this process, past the look for a chip."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    import run as bench_run

    real_load = cells.load_driver

    def load(name):
        module = real_load(name)
        if name == "served_fold":
            prepare(module)
        return module

    monkeypatch.setattr(cells, "load_driver", load)
    args = types.SimpleNamespace(workload=CELL, seed=21, seconds=3.0, trace=0, rehearse=True,
                                 control=False)
    return bench_run, args


def _result(capsys):
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("uid", [5, 8000])  # a live actor called garbage, and the other way
def test_a_reference_with_one_uid_flipped_is_not_correct(uid, capsys, monkeypatch):
    def prepare(module):
        reference_garbage = module.Driver._reference_garbage

        def flipped(self):
            garbage = reference_garbage(self)
            garbage[uid] = not garbage[uid]
            return garbage

        monkeypatch.setattr(module.Driver, "_reference_garbage", flipped)

    bench_run, args = _run_here(monkeypatch, prepare)
    assert bench_run.run(args) == 0
    result, out = _result(capsys)
    assert result["correct"] is False
    failed = [line for line in out.splitlines() if "NOT CORRECT" in line]
    assert any("first_verdict_freed_uids_differing_from_reference_garbage: 1 " in l for l in failed)
    assert any("uids_held_differing_from_reference_live_set: 1 " in l for l in failed)
    assert not any("sessions_not_stopped" in l for l in failed), "the local side was not at fault"


def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """From the window's first moment on the backend's trace marks every
    actor: nothing is garbage any more, so no released session is stopped
    (``test_cells.py``'s break of ``served``, which its ``BREAKS`` table
    cannot hold for a new driver)."""
    def prepare(module):
        window = module.Driver.window

        def broken_window(self, seconds):
            graph = self.system.engine.bookkeeper.shadow_graph
            graph.compute_marks = lambda: np.ones(graph.flags.shape[0], dtype=bool)
            return window(self, seconds)

        monkeypatch.setattr(module.Driver, "window", broken_window)

    bench_run, args = _run_here(monkeypatch, prepare)
    assert bench_run.run(args) == 0
    result, _ = _result(capsys)
    assert result["correct"] is False and result["failed"] > 0


def test_a_program_without_the_hold_is_refused_at_once(capsys, monkeypatch):
    from uigc_tpu.engines.crgc.engine import CRGC

    monkeypatch.delattr(CRGC, "hold_traces")
    bench_run, args = _run_here(monkeypatch, lambda module: None)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="no way to hold traces"):
        bench_run.run(args)
    assert time.perf_counter() - t0 < 20.0
    assert "set-up generate" not in capsys.readouterr().out, "the graph was built first"


def test_the_new_readers_read_a_hand_made_wake_record():
    obs = types.SimpleNamespace(facts={"program_wakes": [
        {"device_s": 0.0, "gated_tiles": 99, "actors_local": 9, "actors_foreign": 9},  # no device call
        {"device_s": 0.1, "gated_tiles": 3, "actors_local": 100_600, "actors_foreign": 5_000_000},
        {"device_s": 0.1, "gated_tiles": 5, "actors_local": 101_112, "actors_foreign": 5_000_000},
        {"device_s": 0.1, "gated_tiles": 4, "actors_local": 100_088, "actors_foreign": 5_000_000},
        {"device_s": 0.2},  # a record read before its counters were
    ]})
    assert cells.reader_of("layers", "gated_tiles.served10m")(obs) == 4
    assert cells.reader_of("layers", "actors_local.served10m")(obs) == 100_600
    assert cells.reader_of("layers", "actors_foreign.served10m")(obs) == 5_000_000
    # a program without the counters (the parent's records): nothing, and no raise
    bare = types.SimpleNamespace(facts={"program_wakes": [{"device_s": 0.1, "upload_bytes": 9e6}]})
    for name in ("gated_tiles", "actors_local", "actors_foreign"):
        assert cells.reader_of("layers", name + ".served10m")(bare) is None
        assert cells.reader_of("layers", name + ".served10m")(types.SimpleNamespace(facts={})) is None
    assert cells.reader_of("layers", "upload_mb.served10m")(bare) == pytest.approx(9.0)
