"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on a trace recorded on the chip and kept beside this file
(``data/churn_two_wakes.xplane.pb``: TPU v5 lite, the churn cell, a
traced interval of 7.1 s that holds two whole wakes; PR 26)."""

import os

import pytest

from harness import cell as cells
from harness import trace
from harness.obs import Obs

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "churn_two_wakes.xplane.pb")


def test_merge_clip_gaps():
    merged = trace.merge([(5, 6), (0, 2), (1, 3), (3, 3), (2.5, 2.75)])
    assert merged == [(0, 3), (5, 6)]
    assert trace.length(merged) == 4
    assert trace.gaps(merged, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert trace.gaps([], 0, 2) == [(0, 2)]
    assert trace.clip([(0, 3), (5, 6), (9, 10)], 1, 5.5) == [(1, 3), (5, 5.5)]
    assert trace.overlap((0, 2), (1, 5)) == 1
    assert trace.overlap((0, 1), (2, 3)) == 0


def test_self_seconds_takes_the_children_out():
    events = [("while", 0.0, 10.0), ("a", 1.0, 4.0), ("b", 4.0, 6.0), ("inner", 4.5, 5.0),
              ("after", 10.0, 11.0)]
    assert trace.self_seconds(events) == [5.0, 3.0, 1.5, 0.5, 1.0]


@pytest.mark.parametrize("name, want", [
    ("%fusion.132 = s32[10000001]{0:T(1024)} fusion(s32[10000001]{0:T(1024)S(1)} %x), kind=kCustom",
     "%fusion.132 fusion"),
    ("%body.9 = f32[78144,128]{1,0:T(8,128)} custom-call(s32[40]{0:T(128)S(1)} %g)",
     "%body.9 custom-call"),
    ("%while.8 = (pred[]{:T(512)}, s32[40]{0:T(128)}) while((pred[]{:T(512)}) %t), condition=%c",
     "%while.8 while"),
    ("jit_wake_fn(123)", "jit_wake_fn(123)"),
])
def test_short_name(name, want):
    assert trace.short_name(name) == want


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(DATA)


def test_recorded_trace_window_and_busy(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(7.095936313, rel=1e-9)
    assert summary.busy_s == pytest.approx(6.872714768, rel=1e-9)
    assert 0 < summary.busy_s < summary.window_s


def test_recorded_trace_kernel_events_and_wakes(summary):
    seconds, events = summary.seconds_of(r" custom-call\(")
    assert events == 94
    assert seconds == pytest.approx(0.966892863, rel=1e-9)
    assert summary.spans_inside("wake") == 2
    assert summary.spans_inside("layout") == 2
    assert summary.spans_inside("no-such-span") == 0


def test_recorded_trace_breakdown(summary):
    names = [row[0] for row in summary.device_ops]
    assert names[0] == "%fusion.132 fusion"
    assert "%body.9 custom-call" in names
    assert not any(name.startswith("%while") for name in names[:7])  # self time, not the loop's
    assert len(summary.device_ops) <= 10 and len(summary.idle_gaps) <= 10
    # self times add up to no more than the device was busy
    assert sum(row[1] for row in summary.device_ops) <= summary.busy_s
    gaps = dict(map(tuple, summary.idle_gaps))
    assert gaps["bench:layout"] == pytest.approx(0.206941555, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(summary.window_s - summary.busy_s, rel=1e-9)


def test_the_readers_on_the_recorded_trace(summary):
    obs = Obs()
    obs.trace = summary
    kernel = cells.load_module(os.path.join(cells.BENCH_DIR, "layers", "kernel_ms.py"), "k")
    idle = cells.load_module(os.path.join(cells.BENCH_DIR, "layers", "device_idle_pct.py"), "i")
    assert kernel.read(obs) == pytest.approx(966.892863 / 2, rel=1e-9)
    assert idle.read(obs) == pytest.approx(100 * (1 - 6.872714768 / 7.095936313), rel=1e-9)
    # a reader that finds nothing to read returns nothing
    assert kernel.read(Obs()) is None and idle.read(Obs()) is None


def test_a_trace_without_the_marks_is_refused(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    with pytest.raises(ValueError):
        trace.summarize(trace.newest_xplane(str(tmp_path)))
