"""Every cell of BENCHMARK.json end to end on the CPU (``--rehearse``),
its control, and a run whose timed path is broken underneath."""

import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH_DIR, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BENCH = json.load(fh)
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
#: one cell per driver is enough for the control and the broken path
BY_DRIVER = {}
for w in BENCH["workloads"]:
    cfg = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        BY_DRIVER.setdefault(json.load(fh)["driver"], w["name"])


def run_cell(*args):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), *args],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    lines = proc.stdout.strip().splitlines()
    return proc, lines


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_rehearsal_runs_end_to_end(workload, trace):
    proc, lines = run_cell("--workload", workload, "--seed", "2147483659",
                           "--seconds", "3", "--trace", trace, "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True
    assert "metrics" not in result, "a CPU run reports no metric"
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    entries = BENCH["per_layer"] if trace == "1" else BENCH["end_to_end"]
    known = {m["name"] for m in entries if workload in m.get("workloads", [workload])}
    assert set(result["rehearsed_metrics"]) <= known
    if trace == "0":
        assert set(result["rehearsed_metrics"]) == known
    # every line before the last names the platform, kind and device count
    assert all("cpu/cpu/x1" in line for line in lines[:-1])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_without_a_chip_there_is_no_result(workload):
    proc, lines = run_cell("--workload", workload, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in lines)


@pytest.mark.parametrize("workload", sorted(BY_DRIVER.values()))
@pytest.mark.parametrize("seed", ["11", "12", "2147483700"])
def test_the_control_comes_out_not_correct(workload, seed):
    proc, lines = run_cell("--workload", workload, "--seed", seed, "--seconds", "3",
                           "--trace", "0", "--rehearse", "--control")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["control"] is True
    assert result["correct"] is False


def _break_tracer_wake(driver):
    """The layout is handed every second transition only: a part of each
    batch is left out."""
    apply_log = driver.tracer.apply_log
    driver.tracer.apply_log = lambda log: apply_log(log[::2])


def _break_served(driver):
    """The backend's trace marks every actor: nothing is ever garbage,
    so no released session is stopped."""
    import numpy as np

    graph = driver.system.engine.bookkeeper.shadow_graph
    graph.compute_marks = lambda: np.ones(graph.flags.shape[0], dtype=bool)


BREAKS = {"tracer_wake": _break_tracer_wake, "served": _break_served}


@pytest.mark.parametrize("driver_name", sorted(BY_DRIVER))
def test_a_broken_timed_path_is_not_correct(driver_name, capsys, monkeypatch):
    """Drive a whole run in this process, past the look for a chip, with
    the program's timed path broken from the window's first moment on."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    import run as bench_run
    from harness import cell as cells

    real_load = cells.load_driver

    def load_broken(name):
        module = real_load(name)
        window = module.Driver.window

        def broken_window(self, seconds):
            BREAKS[name](self)
            return window(self, seconds)

        monkeypatch.setattr(module.Driver, "window", broken_window)
        return module

    monkeypatch.setattr(cells, "load_driver", load_broken)
    args = types.SimpleNamespace(workload=BY_DRIVER[driver_name], seed=21, seconds=3.0,
                                 trace=0, rehearse=True, control=False)
    assert bench_run.run(args) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False
