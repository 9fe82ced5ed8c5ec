"""Configuration ``kron-s22``: the cell by name, the generator against the
program's, the driver's verdict against the plain reference, the cell's
rehearsal, its control, a run whose timed path is broken underneath, and
the four readers that are new with it."""

import json
import os
import sys
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT

import graphgen_kron
import reference
from harness import cell as cells
from test_cells import run_cell

CELL = "kron-s22.rederive"
COPIES = ("wake_ms", "kernel_ms", "frontier_ms", "jump_ms", "repair_sweeps", "jump_sweeps",
          "compiles_in_window", "device_idle_pct", "kernel_steps_pct", "kernel_contract_pct")
NEW = ("dirty_chunks_pct", "pull_sweeps", "tiles_skipped", "kernel_chunk_walks")


def test_the_cell_loads_by_name_with_its_readers():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config["driver"] == "tracer_wake_kron"
    assert cell.config["graph"] == {"generator": "kron", "scale": 22, "edgefactor": 16, "roots": 64}
    assert cell.config["reduced"] == ["scale"] and cell.config["graph_seed"] == 0
    assert [m.name for m in cell.end_to_end] == ["rederive_p50_ms", "rederived_per_s", "setup_s"]
    assert sorted(m.name for m in cell.per_layer) == sorted(n + ".kron" for n in COPIES + NEW)
    # the traffic is the file the other two re-derivation cells run, unedited
    assert cell.traffic == cells.load_cell("powerlaw-10m.rederive").traffic
    assert cells.load_cell(CELL, rehearse=True).config["graph"]["scale"] == 12
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = [c for c in json.load(fh)["configs"] if c["name"] == "kron-s22"][0]
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cell.config["reduced"]


@pytest.mark.parametrize("scale,seed,edgefactor,roots", [(12, 0, 16, 64), (10, 2**31 + 7, 16, 64), (9, 5, 4, 8)])
def test_generator_is_the_programs_and_garbage_is_no_slot_range(scale, seed, edgefactor, roots):
    from uigc_tpu.models.graphgen import kron_actor_graph

    driver = cells.load_driver("tracer_wake_kron")
    mine = driver.kron_with_verdict(scale=scale, seed=seed, edgefactor=edgefactor, roots=roots)
    theirs = kron_actor_graph(scale, seed, edgefactor, roots)
    assert set(mine) == set(theirs)
    for key in mine:
        assert np.array_equal(mine[key], theirs[key]), key
    assert set(graphgen_kron.kron(scale, seed, edgefactor, roots)) == set(driver.GRAPH_KEYS)
    # the linear reference's verdict is the plain reference's
    marks = reference.trace_marks(*(mine[k] for k in driver.GRAPH_KEYS))
    garbage = reference.garbage(mine["flags"], marks)
    assert np.array_equal(garbage, mine["expected_garbage"])
    assert mine["n_live"] + mine["n_garbage"] == 1 << scale == garbage.size
    # n_live is a count and no boundary: garbage below it, live actors above it
    assert garbage[: mine["n_live"]].any() and not garbage[mine["n_live"]:].all()
    assert np.count_nonzero(np.diff(garbage)) > garbage.size // 16


def test_rehearsal_is_correct_and_the_control_is_not():
    proc, lines = run_cell("--workload", CELL, "--seed", "4400000001", "--seconds", "2",
                           "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    # what a CPU run can count: every program counter's reader, the new four among them
    counted = result["rehearsed_metrics"]
    for name in ("repair_sweeps", "jump_sweeps", "kernel_steps_pct", "kernel_contract_pct") + NEW:
        assert name + ".kron" in counted, name
    assert counted["dirty_chunks_pct.kron"]["value"] == 100.0  # one walk chunk at this size
    assert counted["kernel_chunk_walks.kron"]["value"] >= 1
    assert 0 < counted["kernel_contract_pct.kron"]["value"] < 100
    assert any("the program's generator against the benchmark's" in line for line in lines)

    proc, lines = run_cell("--workload", CELL, "--seed", "4400000002", "--seconds", "2",
                           "--trace", "0", "--rehearse", "--control")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["control"] is True and result["correct"] is False
    # the one release the reference alone saw orphans the actor that owed its life to it
    differing = [int(line.split(": ")[1].split(" ")[0]) for line in lines
                 if "check last_wake_verdicts_differing_from_reference" in line]
    assert len(differing) == 1 and differing[0] >= 1


def test_churn_traffic_is_refused():
    proc, _ = run_cell("--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse")
    assert proc.returncode == 0
    driver = cells.load_driver("tracer_wake_kron")
    ctx = types.SimpleNamespace(traffic={"rederive": False}, obs=None, config={})
    with pytest.raises(SystemExit, match="re-derivation traffic only"):
        driver.Driver(ctx).setup()


def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """``test_cells.py``'s broken run, for this driver (its ``BREAKS`` knows
    the drivers it was written with): from the window's first moment the
    wake is handed flags in which the largest hub has halted, so the
    program's verdicts are right for another graph."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    import run as bench_run

    real_load = cells.load_driver

    def load_broken(name):
        module = real_load(name)
        if name != "tracer_wake_kron":
            return module
        window = module.Driver.window

        def broken_window(self, seconds):
            import jax

            flags = self.g["flags"].copy()
            live = ~self.g["expected_garbage"]
            holds = np.bincount(self.g["edge_src"], minlength=self.n) * live
            flags[np.argmax(holds)] |= reference.FLAG_HALTED
            self.flags_dev = jax.device_put(flags)
            return window(self, seconds)

        monkeypatch.setattr(module.Driver, "window", broken_window)
        return module

    monkeypatch.setattr(cells, "load_driver", load_broken)
    args = types.SimpleNamespace(workload=CELL, seed=4400000003, seconds=2.0, trace=0,
                                 rehearse=True, control=False)
    assert bench_run.run(args) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False


# --------------------------------------------------------------------- #
# the readers that are new with the cell, on hand-made wake_stats records
# --------------------------------------------------------------------- #

WAKE = {"n_sweeps": 7, "dirty_chunks": [15, 16, 16, 16, 16, 16, 3], "pull_on": [1, 1, 1, 1, 1, 1, 0],
        "tiles_skipped": [0, 2, 40, 300, 900, 1000, 0], "kernel_chunk_walks": 262211,
        "kernel_steps": 115463, "kernel_contractions": 72485}
#: a program before PR 27: sweep counts only
OLD_WAKE = {"n_sweeps": 7, "closure_sweeps": 0}


def layer(name):
    path = os.path.join(BENCH_DIR, "layers", name + ".kron.py")
    return cells.load_module(path, "test_layer_" + name)


def test_new_readers_on_a_hand_made_record():
    assert layer("dirty_chunks_pct").of([WAKE], 16) == 100.0 * 98 / (7 * 16) == 87.5
    assert layer("pull_sweeps").of([WAKE]) == 6
    assert layer("tiles_skipped").of([WAKE]) == 2242
    assert layer("kernel_chunk_walks").of([WAKE]) == 262211
    # the median over wakes
    other = dict(WAKE, dirty_chunks=[16] * 7, pull_on=[1] * 7, tiles_skipped=[0] * 7, kernel_chunk_walks=5)
    assert layer("dirty_chunks_pct").of([WAKE, other, other], 16) == 100.0
    assert layer("pull_sweeps").of([WAKE, other, other]) == 7
    assert layer("tiles_skipped").of([other, WAKE, WAKE]) == 2242
    assert layer("kernel_chunk_walks").of([other, other, WAKE]) == 5
    # a wake of more sweeps than the program keeps rows for is left out of the share
    long = dict(WAKE, n_sweeps=40, dirty_chunks=[1] * 32)
    assert layer("dirty_chunks_pct").of([long, WAKE], 16) == 87.5
    assert layer("dirty_chunks_pct").of([long], 16) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_nothing_without_their_counter(name):
    module = layer(name)
    extra = (16,) if name == "dirty_chunks_pct" else ()
    assert module.of([OLD_WAKE], *extra) is None
    assert module.of([], *extra) is None and module.of(None, *extra) is None
    if name == "dirty_chunks_pct":  # and nothing where the driver left no chunk count
        assert module.of([WAKE], None) is None
    # through ``read``: an observation with no wake span finds no tracer to ask
    from harness.obs import Obs

    assert module.read(Obs()) is None


@pytest.mark.parametrize("name", COPIES)
def test_copies_read_through_the_originals(name):
    original = "jump_sweeps.chain" if name == "jump_sweeps" else name
    defined_in = layer(name).read.__code__.co_filename
    assert defined_in == os.path.join(BENCH_DIR, "layers", original + ".py")
