"""The reader of the program's own names (``harness/program_trace.py``): on a
made-up plane written with the wire format's four rules, on the trace PR 26
recorded from a program that names nothing (every reader returns ``None``),
and on a trace recorded on the chip from the program with its scopes and
annotations (``data/wake_scopes.xplane.pb``: TPU v5 lite, a 200,000-actor
tracer, three churn wakes bracketed by a ``WakeProfiler``; PR 27)."""

import os
import struct

import pytest

from harness import program_trace as ptr
from harness import trace
from harness.obs import Obs

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PARENT = os.path.join(DATA, "churn_two_wakes.xplane.pb")
SCOPED = os.path.join(DATA, "wake_scopes.xplane.pb")


# --------------------------------------------------------------------- #
# a made-up XSpace
# --------------------------------------------------------------------- #


def varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def stat(meta_id: int, value) -> bytes:
    if isinstance(value, float):
        return field(1, meta_id) + field(2, value)
    return field(1, meta_id) + (field(4, value) if isinstance(value, int) else field(5, value))


def plane(name, stat_names, metadata, lines) -> bytes:
    """``metadata``: {id: (name, {stat id: value})}; ``lines``: {name:
    (timestamp_ns, [(metadata id, offset_ps, duration_ps, {stat id: value})])}."""
    body = field(2, name)
    for sid, sname in stat_names.items():
        body += field(5, field(1, sid) + field(2, field(1, sid) + field(2, sname)))
    for mid, (mname, stats) in metadata.items():
        meta = field(1, mid) + field(2, mname)
        for sid, value in stats.items():
            meta += field(5, stat(sid, value))
        body += field(4, field(1, mid) + field(2, meta))
    for lname, (t0, events) in lines.items():
        line = field(2, lname) + field(3, t0)
        for mid, off, dur, stats in events:
            ev = field(1, mid) + field(2, off) + field(3, dur)
            for sid, value in stats.items():
                ev += field(4, stat(sid, value))
            line += field(4, ev)
        body += field(3, line)
    return field(1, body)


MS = 10 ** 9  # picoseconds


@pytest.fixture()
def made_up(tmp_path):
    """Device: two wakes of the module ``jit_wake_fn`` (10-50 ms and 60-100
    ms), each ``while`` (30 ms) holding a kernel (10), a jump gather (12)
    and a dirty fusion (5), then an unscoped copy (4); a ``jit_other`` op
    between them.  Host: the marks, ``bench:wake`` around each module, and
    ``uigc:wake`` > ``uigc:device`` around the first."""
    tf_op = 7
    ops = {
        1: ("%while.1 = (s32[]) while(%t), condition=%c", "jit(wake_fn)/uigc.wake/repair/while:"),
        2: ("%uigc_propagate.9 = f32[8,128] custom-call(%a)",
            "jit(wake_fn)/uigc.wake/repair/while/body/push/uigc_propagate/pallas_call:"),
        3: ("%fusion.3 = s32[10] fusion(%j), kind=kCustom",
            "jit(wake_fn)/uigc.wake/repair/while/body/jump/gather:"),
        4: ("%fusion.4 = s32[10] fusion(%t), kind=kLoop",
            "jit(wake_fn)/uigc.wake/repair/while/body/dirty/cumsum:"),
        5: ("%copy.5 = s32[10] copy(%p)", ""),
        6: ("%fusion.6 = s32[10] fusion(%x), kind=kLoop", "jit(other)/jumpy/add:"),
        10: ("jit_wake_fn(77)", ""), 11: ("jit_other(78)", ""),
    }
    metadata = {mid: (name, {tf_op: op} if op else {}) for mid, (name, op) in ops.items()}
    op_events, module_events = [], []
    for base in (10, 60):
        module_events.append((10, base * MS, 40 * MS, {}))
        op_events += [(1, base * MS, 30 * MS, {}), (2, (base + 1) * MS, 10 * MS, {}),
                      (3, (base + 12) * MS, 12 * MS, {}), (4, (base + 24) * MS, 5 * MS, {}),
                      (5, (base + 32) * MS, 4 * MS, {})]
    module_events.append((11, 52 * MS, 5 * MS, {}))
    op_events.append((6, 52 * MS, 5 * MS, {}))
    device = plane("/device:TPU:0", {tf_op: "tf_op"}, metadata,
                   {"XLA Modules": (0, module_events), "XLA Ops": (0, sorted(op_events, key=lambda e: e[1]))})
    host_meta = {1: ("bench:traced", {}), 2: ("bench:wake", {}), 3: ("uigc:wake", {}),
                 4: ("uigc:device", {}), 5: ("SomethingElse", {})}
    host = plane("/host:CPU", {9: "wake"}, host_meta, {
        "python3": (0, [(1, 0, 1 * MS, {}), (2, 9 * MS, 42 * MS, {}), (2, 59 * MS, 42 * MS, {}),
                        (5, 3 * MS, 1 * MS, {}), (1, 110 * MS, 1 * MS, {})]),
        "collector": (0, [(3, 5 * MS, 50 * MS, {9: 4}), (4, 8 * MS, 44 * MS, {9: 4})]),
    })
    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(device + host)
    return str(path)


def obs_of(path):
    obs = Obs()
    obs.facts["xplane"] = path
    obs.trace = trace.summarize(path)
    return obs


def test_made_up_plane_scopes_and_annotations(made_up):
    obs = obs_of(made_up)
    assert obs.trace.spans_inside("wake") == 2
    tr = ptr.program_trace(obs)
    assert tr.has_scopes and len(tr.wake_ops[0]) == 10  # jit_other's op is not the wake's
    # self time: the while keeps 30 - 10 - 12 - 5 = 3 ms of its own
    assert ptr.scope_ms_per_wake(obs, "kernel") == pytest.approx(10.0)
    assert ptr.scope_ms_per_wake(obs, "jump") == pytest.approx(12.0)
    assert ptr.scope_ms_per_wake(obs, "frontier") == pytest.approx(5.0 + 3.0)
    assert ptr.scope_ms_per_wake(obs, "repair") == pytest.approx(30.0)
    assert ptr.scope_ms_per_wake(obs, "closure") == pytest.approx(0.0)
    assert ptr.coverage(obs) == pytest.approx(30.0 / 34.0)  # the copy has no scope
    # the collector's annotations, with the wake's ordinal
    assert tr.annotations["uigc:wake"] == [(pytest.approx(0.005), pytest.approx(0.055), 4)]
    # device busy inside uigc:device (8-52 ms): module 1 (34 of 10-44 ms busy)
    assert ptr.annotation_busy_ms(obs, "uigc:device") == pytest.approx(34.0)
    # idle in 0-111 ms: 111 - 34 - 34 - 5 = 38 ms; of it inside uigc:wake (5-55 ms):
    # 5-10, 40-42 and 46-52
    assert tr.idle_share_inside("uigc:wake") == pytest.approx(13.0 / 38.0)


def test_a_program_that_names_nothing_gives_none():
    obs = obs_of(PARENT)
    tr = ptr.program_trace(obs)
    assert not tr.has_scopes and tr.annotations == {}
    assert len(tr.wake_ops[0]) > 1000  # the wake module's operations are found all the same
    ops = {op.scope for op in tr.wake_ops[0]}
    assert "jit(wake_fn)/while/body/pallas_call:" in ops  # the metadata's tf_op is read
    for kind in ptr.KINDS:
        assert ptr.scope_ms_per_wake(obs, kind) is None
    assert ptr.coverage(obs) is None
    assert ptr.annotation_busy_ms(obs, "uigc:device") is None
    assert ptr.phase_ms(obs, "layout") is None
    obs.facts["program_wakes"] = [{"device_s": 0.02, "phases": {"fold": 0.003}}]
    assert ptr.phase_ms(obs, "layout") is None and ptr.phase_ms(obs, "fold") == pytest.approx(3.0)
    assert ptr.sweeps_per_wake(obs, "n_sweeps") is None  # no wake span recorded


def test_without_a_trace_every_trace_reader_gives_none():
    obs = Obs()
    assert ptr.program_trace(obs) is None
    assert ptr.scope_ms_per_wake(obs, "jump") is None and ptr.coverage(obs) is None
    assert ptr.annotation_busy_ms(obs, "uigc:device") is None


def test_recorded_trace_of_the_scoped_program():
    """TPU v5 lite, 200,000 actors, three churn wakes (7 repair sweeps and
    8-9 closure sweeps each, by the program's own count), each bracketed
    by a ``WakeProfiler`` wake and its phases."""
    obs = obs_of(SCOPED)
    assert obs.trace.spans_inside("wake") == 3
    tr = ptr.program_trace(obs)
    assert tr.has_scopes
    for kind, ms in [("kernel", 18.060452629999972), ("jump", 44.98581299466672),
                     ("frontier", 0.09593125133330188), ("closure", 10.278495755999868),
                     ("repair", 52.84452151133345), ("all", 63.20850864533327)]:
        assert ptr.scope_ms_per_wake(obs, kind) == pytest.approx(ms, rel=1e-9), kind
    assert ptr.coverage(obs) == pytest.approx(0.9989509043836907, rel=1e-9)
    # the kernel's events carry the pallas_call's name and its loop's scope
    kernels = {op.scope for op in tr.wake_ops[0] if op.name.startswith("%uigc_propagate")}
    assert kernels == {
        f"jit(wake_fn)/uigc.wake/{loop}/while/body/push/uigc_propagate/pallas_call:"
        for loop in ("closure", "repair")
    }
    # the profiler's annotations: every phase of wake k carries wake=k and lies inside it
    for name in ("wake", "trace", "layout", "upload", "device", "readback"):
        assert [w for _, _, w in tr.annotations["uigc:" + name]] == [0, 1, 2], name
    for (a, b, _), (c, d, _) in zip(tr.annotations["uigc:wake"], tr.annotations["uigc:device"]):
        assert a <= c and d <= b
    # the device ran for 62-64 ms inside each 64-66 ms uigc:device bracket
    assert ptr.annotation_busy_ms(obs, "uigc:device") == pytest.approx(62.629085109999814, rel=1e-9)
    for a, b, _ in tr.annotations["uigc:device"]:
        assert 0.9 * (b - a) < tr.busy_inside((a, b)) <= b - a
    assert tr.idle_share_inside("uigc:wake") == pytest.approx(0.9985446554360905, rel=1e-9)
