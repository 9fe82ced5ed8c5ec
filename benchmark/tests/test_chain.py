"""Configuration ``chain-1m``: the linear reference against the plain one,
the generator against the program's, the cell's rehearsal, its control, a
run whose timed path is broken underneath, and the jump's byte count."""

import json
import sys
import types

import numpy as np
import pytest

from conftest import BENCH_DIR

import graphgen_chain
import reference
import reference_bfs
import roofline_jump
from test_cells import run_cell

CELL = "chain-1m.rederive"


def wrinkled_graph(seed):
    """A random graph with every wrinkle the semantics know: halted and
    busy actors, undelivered messages, actors not interned or not in use,
    references with a count of zero or less, supervisors."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 400))
    m = int(rng.integers(0, 4 * n))
    flags = np.zeros(n, np.uint8)
    for flag, share in ((reference.FLAG_ROOT, 0.05), (reference.FLAG_BUSY, 0.03),
                        (reference.FLAG_INTERNED, 0.9), (reference.FLAG_HALTED, 0.1),
                        (reference.FLAG_IN_USE, 0.9), (reference.FLAG_LOCAL, 0.5)):
        flags |= np.where(rng.random(n) < share, flag, 0).astype(np.uint8)
    recv = np.where(rng.random(n) < 0.03, rng.integers(-2, 3, n), 0).astype(np.int64)
    supervisor = np.where(rng.random(n) < 0.7, rng.integers(0, n, n), -1).astype(np.int32)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    weight = rng.integers(-1, 3, m).astype(np.int64)
    return flags, recv, supervisor, src, dst, weight


@pytest.mark.parametrize("wide", [1, 8, 512], ids=lambda w: f"wide{w}")
@pytest.mark.parametrize("seed", range(0, 120, 3))
def test_linear_reference_equals_the_plain_one(seed, wide, monkeypatch):
    monkeypatch.setattr(reference_bfs, "WIDE", wide)  # both expansions, and the switch between
    graph = wrinkled_graph(seed)
    assert np.array_equal(reference_bfs.trace_marks(*graph), reference.trace_marks(*graph))


def test_linear_reference_on_graphs_with_nothing_to_do():
    none = np.zeros(0, np.int32)
    flags = np.array([reference.FLAG_IN_USE | reference.FLAG_INTERNED], np.uint8)
    lone = (flags, np.zeros(1, np.int64), np.array([-1], np.int32), none, none, none.astype(np.int64))
    assert reference_bfs.trace_marks(*lone).tolist() == [False]
    lone[0][0] |= reference.FLAG_ROOT
    assert reference_bfs.trace_marks(*lone).tolist() == [True]


@pytest.mark.parametrize("n,fraction", [(4096, 0.5), (1000, 0.25), (9, 0.0), (1, 0.5)])
def test_generator_is_the_programs_and_the_reference_finds_its_partition(n, fraction):
    from uigc_tpu.models.graphgen import chain_actor_graph

    mine, theirs = graphgen_chain.chain(n, 0, fraction), chain_actor_graph(n, fraction)
    assert set(mine) == set(theirs)
    for key in mine:
        assert np.array_equal(mine[key], theirs[key]), key
    marks = reference_bfs.trace_marks(*(mine[k] for k in (
        "flags", "recv_count", "supervisor", "edge_src", "edge_dst", "edge_weight")))
    assert np.array_equal(reference.garbage(mine["flags"], marks), mine["expected_garbage"])


def test_jump_bytes_on_a_hand_counted_case():
    # 64 actors, one doubling: hits 3 x 64, the doubling 4 x 64, the pack
    # 64 + 4 words of 32 bits x 2: 192 + 256 + 72 = 520 elements of 4 bytes
    assert roofline_jump.elements_per_sweep(64, steps=1) == 520
    assert roofline_jump.jump_bytes(64, jump_sweeps=1, steps=1) == 2080
    assert roofline_jump.jump_bytes(64, jump_sweeps=3, steps=1) == 6240
    # the program's two doublings: 12.125 elements, 48.5 bytes an actor a sweep
    assert roofline_jump.jump_bytes(1_000_000, jump_sweeps=12, steps=2) == 48.5e6 * 12
    assert roofline_jump.jump_bytes(1_000_000, jump_sweeps=12) == 48.5e6 * 12  # JUMP_STEPS is 2
    # no share of a roofline over 100%: at the peak, 1M actors take 59 us a sweep
    assert roofline_jump.jump_bytes(1_000_000, 1) / 819e9 < 60e-6


def test_rehearsal_is_correct_and_the_control_is_not():
    proc, lines = run_cell("--workload", CELL, "--seed", "2900000001", "--seconds", "2",
                           "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    # what a CPU run can count: the jump engaged, in fewer sweeps than push needs hops
    counted = result["rehearsed_metrics"]
    assert counted["jump_sweeps.chain"]["value"] >= 1
    assert counted["repair_sweeps.chain"]["value"] < 100 < 2047
    assert "jump_hbm_pct.chain" not in counted, "a CPU has no peak to take a share of"
    proc, lines = run_cell("--workload", CELL, "--seed", "2900000002", "--seconds", "2",
                           "--trace", "0", "--rehearse", "--control")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["control"] is True and result["correct"] is False
    # the one release the reference alone saw cuts the chain in its middle half
    differing = [int(line.split(": ")[1].split(" ")[0]) for line in lines
                 if "check last_wake_verdicts_differing_from_reference" in line]
    assert len(differing) == 1 and 2048 // 4 <= differing[0] <= 3 * 2048 // 4


def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """``test_cells.py``'s broken run, for this driver (its ``BREAKS`` knows
    the drivers it was written with): from the window's first moment the
    wake is handed flags in which one actor of the mid-chain has halted,
    so the program's verdicts are right for another graph."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    import run as bench_run
    from harness import cell as cells

    real_load = cells.load_driver

    def load_broken(name):
        module = real_load(name)
        if name != "tracer_wake_chain":
            return module
        window = module.Driver.window

        def broken_window(self, seconds):
            import jax

            flags = self.g["flags"].copy()
            flags[self.n_live // 2] |= reference.FLAG_HALTED
            self.flags_dev = jax.device_put(flags)
            return window(self, seconds)

        monkeypatch.setattr(module.Driver, "window", broken_window)
        return module

    monkeypatch.setattr(cells, "load_driver", load_broken)
    args = types.SimpleNamespace(workload=CELL, seed=2900000003, seconds=2.0, trace=0,
                                 rehearse=True, control=False)
    assert bench_run.run(args) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False
