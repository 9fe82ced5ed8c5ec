"""The nine readers of a release's life (PR 40): ``ingest_wait_ms``,
``wake_gap_ms``, ``dispatch_ms``, ``stage_ms`` (``.served`` and
``.engine``) and ``stop_cascade_ms.served``, over made-up ``WakeProfiler``
records with the fields, without them (the parent commit's records:
``None``), and with a cascade that had not ended when the driver polled."""

import os

import pytest

from harness import program_trace as ptr
from harness.cell import reader_of
from harness.obs import Obs
from harness.trace import summarize

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "wake_scopes.xplane.pb")

#: metric -> the record field it reads
FIELDS = {
    "ingest_wait_ms": "ingest_wait_s", "wake_gap_ms": "gap_s",
    "dispatch_ms": "dispatch_s", "stage_ms": "stage_s",
}
NAMES = [f"{m}.{cell}" for m in FIELDS for cell in ("served", "engine")] + ["stop_cascade_ms.served"]


def record(device_s=0.02, **fields):
    return {"t": 1.0, "wall_s": 0.03, "device_s": device_s, "phases": {"device": 0.008}, **fields}


def obs_with(records):
    obs = Obs()
    obs.facts["program_wakes"] = records
    return obs


def test_there_are_nine_and_the_benchmark_lists_each_once():
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(DATA)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        listed = [m for m in json.load(fh)["per_layer"] if m["name"] in NAMES]
    assert len(NAMES) == 9 and sorted(m["name"] for m in listed) == sorted(NAMES)
    for m in listed:
        cell = "tree-100k.sessions" if m["name"].endswith(".served") else "engine-fold-10m.flush-20k"
        assert m["workloads"] == [cell] and m["source"] == "program_span" and m["unit"] == "ms"


@pytest.mark.parametrize("name", [n for n in NAMES if n != "stop_cascade_ms.served"])
def test_a_field_reader_takes_the_median_over_the_wakes_that_called_the_device(name):
    field = FIELDS[name.split(".")[0]]
    read = reader_of("layers", name)
    records = [
        record(**{field: 0.004}), record(**{field: 0.010}), record(**{field: 0.006}),
        record(device_s=0.0, **{field: 5.0}),  # an empty timer wake: not counted
        record(**{field: None}),               # nothing to time in that wake
    ]
    assert read(obs_with(records)) == pytest.approx(6.0)
    # the parent's records have no such field, and a timed run has no records
    assert read(obs_with([record(), record()])) is None
    assert read(obs_with([])) is None and read(Obs()) is None


def test_a_cascade_still_running_is_left_out_of_the_median():
    read = reader_of("layers", "stop_cascade_ms.served")
    ended = [record(freed_local=512, stopped=512, cascade_s=s) for s in (0.003, 0.009, 0.005)]
    running = record(freed_local=512, stopped=100, cascade_s=None)
    freed_nothing = record()
    assert read(obs_with(ended + [running, freed_nothing])) == pytest.approx(5.0)
    assert read(obs_with([running, freed_nothing])) is None
    assert read(obs_with([record(device_s=0.0, cascade_s=1.0)])) is None


def test_the_recorded_trace_has_no_dispatch_annotation():
    """PR 27's trace was recorded before ``uigc:dispatch`` and
    ``uigc:stage`` existed: a reader of the device's busy time inside
    them finds nothing there, and ``uigc:device`` is what it was."""
    obs = Obs()
    obs.facts["xplane"] = SCOPED
    obs.trace = summarize(SCOPED)
    assert ptr.annotation_busy_ms(obs, "uigc:dispatch") is None
    assert ptr.annotation_busy_ms(obs, "uigc:stage") is None
    assert ptr.annotation_busy_ms(obs, "uigc:device") == pytest.approx(62.629085109999814, rel=1e-9)
    for name in NAMES:
        assert reader_of("layers", name)(obs) is None
