"""Configuration ``engine-fold-10m``: the row encoder against a row-at-a-time
one, the cell's rehearsal with every per-layer metric a CPU can read, its
refusal without a chip, its control, a run whose timed path is broken
underneath, a system that is slow to start (the collector's own timer must
not wake it), and a program without the foreign path (the parent of the PR
that added it) refused at once."""

import json
import sys
import time
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

from harness.cell import load_driver
from test_cells import BENCH, run_cell

CELL = "engine-fold-10m.flush-20k"
E = 4
#: what only a chip's trace can give
DEVICE_ONLY = {"kernel_ms.engine", "device_busy_ms.engine", "device_idle_pct.engine"}

engine_fold = load_driver("engine_fold")


def rows_one_at_a_time(uids, bits, recv, created, spawned, updated):
    """``encode_rows``'s contract, an actor and a fact at a time."""
    rows = []
    for i, uid in enumerate(uids.tolist()):
        mine = [
            [(int(o), int(t)) for a, o, t in zip(*created) if a == i],
            [int(c) for a, c in zip(*spawned) if a == i],
            [(int(t), int(info)) for a, t, info in zip(*updated) if a == i],
        ]
        first = True
        while first or any(mine):
            r = [-1] * (4 + 5 * E)
            r[1], r[2], r[3] = uid, int(bits[i]), int(recv[i]) if first else 0
            for k, (o, t) in enumerate(mine[0][:E]):
                r[4 + 2 * k], r[5 + 2 * k] = o, t
            for k, c in enumerate(mine[1][:E]):
                r[4 + 2 * E + k] = c
            for k, (t, info) in enumerate(mine[2][:E]):
                r[4 + 3 * E + 2 * k], r[5 + 3 * E + 2 * k] = t, info
            rows.append(r)
            mine = [kind[E:] for kind in mine]
            first = False
    return np.array(rows, dtype=np.int64)


@pytest.mark.parametrize("seed", range(6))
def test_encoder_equals_a_row_at_a_time(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 40))
    uids = np.sort(rng.choice(1000, m, replace=False)).astype(np.int64)
    bits = rng.integers(0, 4, m)
    recv = rng.integers(0, 5, m)

    def facts(k, columns):
        # a few actors with many facts: more than one row
        who = np.minimum(rng.integers(0, m, k), rng.integers(0, m, k)).astype(np.int64)
        return (who,) + tuple(rng.integers(0, 1000, k) for _ in range(columns))

    created, spawned, updated = facts(int(rng.integers(0, 90)), 2), facts(int(rng.integers(0, 60)), 1), \
        facts(int(rng.integers(0, 90)), 2)
    got = engine_fold.encode_rows(uids, bits, recv, created, spawned, updated, E)
    want = rows_one_at_a_time(uids, bits, recv, created, spawned, updated)
    assert got[:, 1:].tolist() == want[:, 1:].tolist()  # column 0 is the plane's to stamp


def test_rehearsal_reports_every_per_layer_metric_a_cpu_can_read():
    proc, lines = run_cell("--workload", CELL, "--seed", "2147483659", "--seconds", "3",
                           "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    named = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    got = result["rehearsed_metrics"]
    assert set(got) == named - DEVICE_ONLY
    assert got["uids_interned.engine"]["value"] == 0, "interning inside the window"
    assert got["compiles_in_window.engine"]["value"] == 0
    assert got["kill_uids.engine"]["value"] == 16  # last_reference_releases_per_wake
    assert got["fold_rows.engine"]["value"] > 1000
    assert got["upload_mb.engine"]["value"] == pytest.approx(8192 * 9e-6)  # flags + recv_count
    text = "\n".join(lines)
    for phase in ("generate", "churn population", "actor system", "encode and fold", "wake 0",
                  "warm-up"):
        assert f"set-up {phase}" in text, phase


def test_timed_run_reports_the_end_to_end_metrics():
    proc, lines = run_cell("--workload", CELL, "--seed", "3", "--seconds", "3", "--trace", "0",
                           "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert set(result["rehearsed_metrics"]) == {"collected_per_s", "setup_s"}
    assert "wake-profile=False" in "\n".join(lines), "the timed run attached the profiler"


def test_without_a_chip_there_is_no_result():
    proc, lines = run_cell("--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in lines)


def test_the_control_comes_out_not_correct():
    proc, lines = run_cell("--workload", CELL, "--seed", "2147483701", "--seconds", "3",
                           "--trace", "0", "--rehearse", "--control")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["control"] is True and result["correct"] is False


def _run_here(monkeypatch, capsys, prepare):
    """A whole run in this process, past the look for a chip."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    import run as bench_run
    from harness import cell as cells

    real_load = cells.load_driver

    def load(name):
        module = real_load(name)
        prepare(module)
        return module

    monkeypatch.setattr(cells, "load_driver", load)
    args = types.SimpleNamespace(workload=CELL, seed=21, seconds=3.0, trace=0, rehearse=True,
                                 control=False)
    return bench_run, args


def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """From the window's first moment on the plane is handed every second
    row of a batch only: flushes are lost on the way to the collector."""
    def prepare(module):
        window = module.Driver.window

        def broken_window(self, seconds):
            write = self.plane.write_foreign
            self.plane.write_foreign = lambda rows: write(rows[::2].copy())
            return window(self, seconds)

        monkeypatch.setattr(module.Driver, "window", broken_window)

    bench_run, args = _run_here(monkeypatch, capsys, prepare)
    assert bench_run.run(args) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False


def test_a_slow_start_lets_no_timer_wake_the_collector(capsys, monkeypatch):
    """The Bookkeeper's timer is armed when the system starts and stopped
    only after.  At the default 50 ms it fires in between once start-up is
    slow; that wake traces the empty graph and answers into the sink, and
    every later answer is taken for the batch after its own."""
    from uigc_tpu.engines.crgc.collector import Bookkeeper

    stop = Bookkeeper.stop_timers

    def slow_stop(self):
        time.sleep(0.3)  # six of the default intervals
        stop(self)

    monkeypatch.setattr(Bookkeeper, "stop_timers", slow_stop)
    bench_run, args = _run_here(monkeypatch, capsys, lambda module: None)
    assert bench_run.run(args) == 0
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True, out[-3000:]
    assert "freed uids=4096 " in out, "the first answer was not the first wake's"


def test_an_answer_nobody_asked_for_fails_the_run(capsys, monkeypatch):
    """A trace the driver did not pace answers into the sink; the next
    batch must not take that answer for its own."""
    def prepare(module):
        window = module.Driver.window

        def window_after_a_stray_trace(self, seconds):
            self._sink(np.empty(0, np.int64), np.empty(0, np.int64))
            return window(self, seconds)

        monkeypatch.setattr(module.Driver, "window", window_after_a_stray_trace)

    bench_run, args = _run_here(monkeypatch, capsys, prepare)
    with pytest.raises(RuntimeError, match="no batch asked for"):
        bench_run.run(args)


def test_a_program_without_the_foreign_path_is_refused_at_once(capsys, monkeypatch):
    from uigc_tpu.engines.crgc.packed import PackedPlane

    monkeypatch.delattr(PackedPlane, "write_foreign")
    bench_run, args = _run_here(monkeypatch, capsys, lambda module: None)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="no foreign path"):
        bench_run.run(args)
    assert time.perf_counter() - t0 < 20.0
    assert "set-up generate" not in capsys.readouterr().out, "the graph was built first"
