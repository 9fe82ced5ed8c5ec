"""Compiled-on-TPU parity tier (``UIGC_TEST_TPU=1 python -m pytest tests/``).

Every test here runs the Pallas trace kernel with ``interpret=False`` on a
real chip, inside the wake program that runs it there, and checks byte-identical marks against the numpy oracle
(reference semantics: ShadowGraph.java:205-289).  The default CPU tier runs
the same kernels in interpret mode only, which cannot catch Mosaic lowering
failures — a kernel can trace fine interpreted and still be uncompilable on
hardware (that exact failure hid the flagship kernel for three rounds).  A
deliberate kernel break must turn THIS file red on a TPU host.
"""

import numpy as np
import pytest

from uigc_tpu.ops import pallas_decremental, pallas_trace, trace as trace_ops
from test_pallas_incremental import run_history
from test_pallas_trace import random_graph

pytestmark = pytest.mark.tpu

F = trace_ops


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n,n_edges", [(1000, 4000), (20000, 80000)])
def test_compiled_matches_oracle(seed, n, n_edges):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n, n_edges)
    expected = trace_ops.trace_marks_np(*g)
    flags, recv, supervisor, src, dst, w = g
    prep = pallas_trace.prepare_chunks(src, dst, w, supervisor, n)
    got, _ = pallas_decremental.derive(flags, recv, [prep], interpret=False)
    assert np.array_equal(got, expected)


def test_compiled_million_actor_parity():
    """One >=1M-actor case on hardware: the geometry (312k+ word table
    rows, thousands of grid steps) is nothing like the small cases'."""
    n, m = 1_000_000, 4_000_000
    rng = np.random.default_rng(42)
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, np.uint8)
    flags[rng.choice(n, n // 100, replace=False)] |= F.FLAG_ROOT
    flags[rng.choice(n, n // 50, replace=False)] |= F.FLAG_HALTED
    recv = np.zeros(n, np.int64)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = np.ones(m, np.int64)
    sup = np.full(n, -1, np.int32)
    expected = trace_ops.trace_marks_np(flags, recv, sup, src, dst, w)
    prep = pallas_trace.prepare_chunks(src, dst, w, sup, n)
    got, _ = pallas_decremental.derive(flags, recv, [prep], interpret=False)
    assert np.array_equal(got, expected)


def test_compiled_incremental_mutation_sequence():
    """The full tier lifecycle — base pack, delta freeze, consolidation,
    in-place base masking, XLA live tier — compiled at every checkpoint."""
    layout = run_history(
        0,
        n=2500,
        steps=300,
        check_every=60,
        interpret=False,
        freeze_threshold=24,
        max_frozen=2,
    )
    assert layout.stats["rebuilds"] == 1
    assert layout.stats["freezes"] >= 1


def test_compiled_decremental_wakes():
    """The closure+repair wake (dst-gated kernel variant) compiled on
    hardware, diffed against the from-scratch oracle across churn wakes
    incl. a released cycle and a halt cascade."""
    from test_pallas_decremental import OracleGraph, _rand_schedule

    pd = pallas_decremental
    rng = np.random.default_rng(7)
    n = 1 << 12
    g = OracleGraph(rng, n, n_edges=4 * n)
    tracer = pd.DecrementalTracer(
        n, interpret=False, freeze_threshold=64, max_frozen=2
    )
    src, dst, w, sup = g.arrays()
    tracer.rebuild(src, dst, w, sup)
    assert np.array_equal(tracer.marks(g.flags, g.recv), g.oracle_marks())
    for wake in range(4):
        _rand_schedule(rng, g, tracer, k=60)
        got = tracer.marks(g.flags, g.recv)
        assert np.array_equal(got, g.oracle_marks()), f"wake {wake}"
