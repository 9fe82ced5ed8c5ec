"""Device-plane observatory suite (uigc_tpu/telemetry/device.py).

Layers, bottom up:

- attribution math: per-sweep device-time split reconciles with the
  wake's measured device seconds by construction, dirty-chunk weights;
- ledger walk: duck-typed family tallies over host and device arrays,
  map-entry estimates, torn-read tolerance;
- donation audit: true positive on a forced copy (an un-donatable host
  buffer handed to a donating call), negative on a real donation;
- event folding: compile hit/miss streams, transfer phase attribution,
  origin scoping, registry counter names;
- live planes (decremental CPU backend under seeded churn): the
  memory ledger returns to baseline after sweeps free slots (no ledger
  leak), compile counters are exactly 1-miss-then-hits per geometry,
  the transfer accounter stays silent across transfer-free idle wakes,
  per-sweep attribution reconciles with the profiler's device phase
  within 10%, and ``/device`` serves a schema-valid document;
- the acceptance scenario: a deliberately planted regression — a
  per-wake recompile storm AND an un-donated buffer copy — fires
  ``recompile_storm`` and ``donation_copy_detected`` with the correct
  tag/site labels, and ``device_report`` attributes both to the
  correct plane;
- tools: bench_check's DEVICE family SKIPs honestly on the committed
  (CPU-only) trajectory and FAILs on a doctored regressed round;
  uigc_top's device panel degrades to dashes on nodes without the
  observatory; uigc-lint UL011 flags unannotated host transfers and
  honors the ``# readback:`` annotation.
"""

import json
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import bench_check  # noqa: E402
import device_report  # noqa: E402
import uigc_lint  # noqa: E402
import uigc_top  # noqa: E402

from uigc_tpu import (  # noqa: E402
    AbstractBehavior,
    ActorTestKit,
    Behaviors,
    NoRefs,
)
from uigc_tpu.engines.crgc.arrays import audit_donation  # noqa: E402
from uigc_tpu.ops.slotmap import PairLog  # noqa: E402
from uigc_tpu.telemetry.device import (  # noqa: E402
    DeviceObservatory,
    ledger_families,
    validate_device_doc,
)
from uigc_tpu.telemetry.metrics import MetricsRegistry  # noqa: E402
from uigc_tpu.utils import events  # noqa: E402

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def clean_recorder():
    """Telemetry enables the process-global recorder; leave no residue
    for the rest of the suite."""
    yield
    events.recorder.disable()
    events.recorder.reset()
    with events.recorder._lock:
        events.recorder._listeners.clear()


# ------------------------------------------------------------------- #
# Ledger walk
# ------------------------------------------------------------------- #


class _FakeGraph:
    def __init__(self):
        self.flags = np.zeros(1024, np.uint8)
        self.recv_count = np.zeros(1024, np.int64)
        self.edge_src = np.zeros(64, np.int32)
        self.edge_dst = np.zeros(64, np.int32)
        self.edge_weight = np.zeros(64, np.int64)
        self.slot_of = {object(): i for i in range(10)}
        self.send_matrix = {1: 2, 3: 4}
        self._pair_log = PairLog([(True, 1, 2, 0)] * 5)


def test_ledger_families_duck_typed():
    fams = ledger_families(_FakeGraph())
    assert fams["node_features"]["host"] == 1024 * (1 + 8)
    assert fams["edges"]["host"] == 64 * (4 + 4 + 8)
    # maps are entry-count estimates, 10 slots + 2 matrix, and the pair
    # log's own bytes: 5 rows of four int64
    assert fams["maps"]["host"] == (10 + 2) * 96 + 5 * 32
    assert fams["node_features"]["device"] == 0
    # an alien object contributes nothing and never raises
    assert isinstance(ledger_families(object()), dict)


def test_ledger_families_sees_device_arrays():
    import jax

    class G:
        _dev_flags = jax.device_put(np.zeros(256, np.uint8))
        _dev_stacked = {"row_pos": jax.device_put(np.zeros((4, 8), np.int32))}

    fams = ledger_families(G())
    assert fams["device_nodes"]["device"] == 256
    assert fams["device_layout"]["device"] == 4 * 8 * 4


# ------------------------------------------------------------------- #
# Donation audit
# ------------------------------------------------------------------- #


def test_donation_audit_true_positive_on_forced_copy():
    """A host (numpy) buffer handed to a 'donating' call can never be
    aliased — XLA copies.  The audit must flag it with the site label."""
    events.recorder.enable()
    obs = DeviceObservatory(node="")
    events.recorder.add_listener(obs)
    try:
        audit_donation("planted.copy", np.zeros(1024, np.int32))
        assert obs.donations == {"planted.copy": 1}
    finally:
        events.recorder.remove_listener(obs)
        obs.close()


def test_donation_audit_negative_on_real_donation():
    import jax
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def bump(x):
        return x.at[0].add(1)

    donated = jax.device_put(np.zeros(512, np.int32))
    out = bump(donated)
    out.block_until_ready()
    events.recorder.enable()
    obs = DeviceObservatory(node="")
    events.recorder.add_listener(obs)
    try:
        audit_donation("real.donation", donated)
        assert obs.donations == {}
    finally:
        events.recorder.remove_listener(obs)
        obs.close()


# ------------------------------------------------------------------- #
# Event folding + registry metrics
# ------------------------------------------------------------------- #


def test_observatory_folds_events_and_registers_metrics():
    events.recorder.enable()
    registry = MetricsRegistry()
    obs = DeviceObservatory(node="uigc://me", registry=registry)
    try:
        obs(events.COMPILE, {"tag": "t", "geom": "g1", "hit": False,
                             "duration_s": 0.5})
        obs(events.COMPILE, {"tag": "t", "geom": "g1", "hit": True})
        obs(events.COMPILE, {"tag": "t", "geom": "g1", "hit": True})
        obs(events.HOST_TRANSFER, {"site": "s", "bytes": 100, "phase": "trace"})
        obs(events.DONATION_COPY, {"site": "d"})
        # origin scoping: a peer system's event is ignored
        obs(events.COMPILE, {"tag": "peer", "hit": False,
                             "origin": "uigc://other"})
        doc = obs.to_doc()
        assert doc["compile"]["entries"] == [
            {"tag": "t", "geom": "g1", "hits": 2, "misses": 1,
             "compile_s": 0.5}
        ]
        assert doc["transfers"]["total_bytes"] == 100
        assert doc["donation"]["copies_total"] == 1
        snap = registry.snapshot()
        assert snap["uigc_compile_misses_total"]["samples"][0]["value"] == 1
        assert snap["uigc_compile_hits_total"]["samples"][0]["value"] == 2
        assert snap["uigc_host_transfers_total"]["samples"][0]["labels"] == {
            "phase": "trace", "site": "s",
        }
        assert validate_device_doc(doc) == []
    finally:
        obs.close()


def test_compile_streams_bounded_during_storm():
    """A shape-key storm mints a fresh geometry per wake; the
    observatory's per-tag streams must stay bounded (overflow fold, the
    registry's max-labelsets discipline) while the miss count — the
    alert input — keeps growing."""
    obs = DeviceObservatory(node="")
    try:
        for i in range(obs.MAX_GEOMS_PER_TAG + 500):
            obs(events.COMPILE, {"tag": "storm", "geom": f"g{i}", "hit": False})
        entries = obs.to_doc()["compile"]["entries"]
        assert len(entries) == obs.MAX_GEOMS_PER_TAG + 1
        overflow = [e for e in entries if e["geom"] == "overflow"]
        assert overflow and overflow[0]["misses"] == 500
        assert sum(e["misses"] for e in entries) == obs.MAX_GEOMS_PER_TAG + 500
    finally:
        obs.close()


def test_validate_device_doc_rejects_malformed():
    assert validate_device_doc([]) == ["document is not an object"]
    assert any("wakes" in p for p in validate_device_doc({"version": 1}))
    good = DeviceObservatory(node="x")
    try:
        doc = good.to_doc()
        assert validate_device_doc(doc) == []
        doc["recent_wakes"] = [{"n_sweeps": 2, "sweep_dirty_chunks": [1, 1, 1]}]
        assert any("sweep_dirty_chunks" in p for p in validate_device_doc(doc))
    finally:
        good.close()


def test_findings_attribute_planted_planes():
    """The report's explainer names the planted tag/site, worst first."""
    doc = {
        "compile": {"entries": [
            # shape-key churn: one miss per FRESH geometry, same tag
            {"tag": "dec_wake", "geom": f"g{i}", "hits": 0, "misses": 1}
            for i in range(5)
        ]},
        "donation": {"sites": {"mesh.fold": 2}},
        "transfers": {"sites": [
            {"site": "stray", "phase": "fold", "count": 3, "bytes": 999},
            {"site": "marks.decremental", "phase": "trace", "count": 9,
             "bytes": 100},
        ]},
        "ledger": {"families": {}, "peaks": {}},
        "recent_wakes": [],
    }
    flist = device_report.findings(doc)
    assert flist[0]["plane"] == "compile"
    assert flist[0]["label"] == "dec_wake"
    assert flist[0]["severity"] == "critical"
    planes = {f["plane"]: f for f in flist}
    assert planes["donation"]["label"] == "mesh.fold"
    assert planes["transfer"]["label"] == "stray@fold"
    # the accounted trace-phase readback is NOT a finding
    assert not any("marks.decremental" in f["label"] for f in flist)


# ------------------------------------------------------------------- #
# Live planes (decremental CPU backend under churn)
# ------------------------------------------------------------------- #


class _Spawn(NoRefs):
    pass


class _Drop(NoRefs):
    pass


class _Worker(AbstractBehavior):
    def on_message(self, msg):
        return self


def _churn_root(counter):
    class Root(AbstractBehavior):
        def __init__(self, context):
            super().__init__(context)
            self.held = []

        def on_message(self, msg):
            ctx = self.context
            if isinstance(msg, _Spawn):
                base = counter[0]
                counter[0] += 16
                self.held.extend(
                    ctx.spawn(Behaviors.setup(_Worker), f"w{base + i}")
                    for i in range(16)
                )
            elif isinstance(msg, _Drop) and self.held:
                ctx.release(*self.held)
                self.held = []
            return self

    return Root


def _device_kit(extra=None):
    config = {
        "uigc.crgc.wakeup-interval": 10,
        "uigc.crgc.shadow-graph": "decremental",
        "uigc.telemetry.device": True,
        "uigc.telemetry.timeseries": True,
        "uigc.telemetry.ts-sample-interval": 100,
    }
    config.update(extra or {})
    return ActorTestKit(config=config, name="devtest")


def _wait(predicate, timeout_s=30.0, poll_s=0.1):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(poll_s)
    return predicate()


def test_device_observatory_live_planes():
    """One live churn run asserting every plane: ledger no-leak,
    compile 1-miss-then-hits, transfer-free idle wakes, attribution
    reconciliation, and the /device schema."""
    from uigc_tpu.ops import pallas_decremental

    pallas_decremental._fn_cache.clear()  # deterministic miss counts
    counter = [0]
    kit = _device_kit({"uigc.telemetry.http-port": 0})
    try:
        root = kit.spawn(Behaviors.setup_root(_churn_root(counter)), "root")
        obs = kit.system.telemetry.observatory
        assert _wait(lambda: obs.wakes > 0)  # first (cold) wake landed

        def cycle():
            root.tell(_Spawn())
            time.sleep(0.15)
            root.tell(_Drop())
            time.sleep(0.15)

        cycle()
        assert _wait(
            lambda: len(kit.system.engine.bookkeeper.shadow_graph.slot_of)
            <= 6
        )  # churn swept
        baseline = ledger_families(
            kit.system.engine.bookkeeper.shadow_graph
        )
        base_maps = baseline["maps"]["host"]
        base_nodes = baseline["node_features"]["host"]
        for _ in range(4):
            cycle()
        assert _wait(
            lambda: len(kit.system.engine.bookkeeper.shadow_graph.slot_of)
            <= 6
        )
        final = ledger_families(kit.system.engine.bookkeeper.shadow_graph)
        # -- memory ledger: live bytes return to baseline after sweeps
        # free slots; repeated cycles must not trend upward (no leak).
        assert final["maps"]["host"] <= base_maps + 2 * 96
        assert final["node_features"]["host"] == base_nodes  # no capacity growth
        # the peak watermark recorded the churn high-water mark
        doc = obs.to_doc()
        assert doc["ledger"]["peaks"]["maps"] > final["maps"]["host"]

        # -- compile plane: exactly 1 miss then hits per geometry.
        dec_streams = {
            (e["geom"]): e
            for e in doc["compile"]["entries"]
            if e["tag"] == "dec_wake"
        }
        assert dec_streams, doc["compile"]["entries"]
        for geom, entry in dec_streams.items():
            assert entry["misses"] <= 1, (geom, entry)
        assert sum(e["hits"] for e in dec_streams.values()) >= 3

        # -- sweep plane: the wake program's own counters reach the
        # records, and the four phases of the device call lie inside the
        # bracket around them (record["device_s"]) and fill it to within
        # 10% (in the best wake: at this size a call takes milliseconds,
        # and a thread switch between two brackets of a loaded host is
        # not the phases' fault).
        def has_stats_wake():
            return any(
                r.get("n_sweeps") for r in obs.to_doc()["recent_wakes"]
            )

        if not _wait(has_stats_wake, timeout_s=10.0):
            cycle()  # one more repair round if the first ones were trivial
        assert _wait(has_stats_wake, timeout_s=10.0)
        doc = obs.to_doc()
        stats_wakes = [r for r in doc["recent_wakes"] if r.get("n_sweeps")]
        assert stats_wakes
        left_out = []
        for rec in stats_wakes:
            assert len(rec["sweep_dirty_chunks"]) == int(rec["n_sweeps"])
            assert rec["closure_sweeps"] >= 0
            inside = sum(
                rec["phases"][p]
                for p in ("layout", "upload", "device", "readback")
            )
            assert inside <= rec["device_s"]
            left_out.append(1.0 - inside / rec["device_s"])
        assert min(left_out) <= 0.10, left_out

        # -- transfer plane negative case: idle (transfer-free) wakes
        # commit nothing — the graph-dirty gate skips the trace, so the
        # accounter must stay flat while wakes keep happening.
        time.sleep(0.3)  # drain any in-flight cascade
        before = obs.to_doc()
        before_wakes = before["wakes"]
        time.sleep(0.6)
        after = obs.to_doc()
        assert after["wakes"] > before_wakes  # collector kept waking
        assert (
            after["transfers"]["total_count"]
            == before["transfers"]["total_count"]
        )

        # -- /device serves the same schema-valid document.
        port = kit.system.telemetry.http.port
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/device", timeout=10
        ) as rsp:
            served = json.loads(rsp.read())
        assert validate_device_doc(served) == []
        assert served["node"] == kit.system.address

        # the time plane carries the decomposition series
        store = kit.system.telemetry.store
        assert store.range("uigc_device_sweeps", window_s=300)["buckets"]
    finally:
        kit.shutdown()


def test_planted_regression_fires_alerts_with_labels():
    """Acceptance: a forced per-wake recompile (fresh geometry every
    beat, one tag) and an un-donated buffer copy, both injected, must
    fire ``recompile_storm`` and the donation audit with the planted
    tag/site labels — and device_report must attribute both planes."""
    kit = _device_kit()
    try:
        telemetry = kit.system.telemetry
        obs = telemetry.observatory
        engine = telemetry.alerts
        assert engine is not None
        t0 = time.time()
        beats = 0
        while time.time() - t0 < 3.5:
            events.recorder.commit(
                events.COMPILE, tag="planted_storm", geom=f"g{beats}",
                hit=False,
            )
            audit_donation(
                "planted.copy", np.zeros(256, np.int32)
            )
            beats += 1
            time.sleep(0.1)

        def fired():
            active = {
                (a["rule"], tuple(sorted(a["labels"].items())))
                for a in engine.active()
            }
            return (
                ("recompile_storm", (("tag", "planted_storm"),)) in active
                and (
                    "donation_copy_detected",
                    (("site", "planted.copy"),),
                ) in active
            )

        assert _wait(fired, timeout_s=15.0), engine.active()

        doc = obs.to_doc()
        flist = device_report.findings(doc)
        compile_findings = [f for f in flist if f["plane"] == "compile"]
        assert any(f["label"] == "planted_storm" for f in compile_findings)
        donation_findings = [f for f in flist if f["plane"] == "donation"]
        assert any(f["label"] == "planted.copy" for f in donation_findings)
        # the planes carried the planted labels all the way through
        assert doc["donation"]["sites"]["planted.copy"] == beats
        storm = [
            e for e in doc["compile"]["entries"]
            if e["tag"] == "planted_storm"
        ]
        assert len(storm) == beats  # one fresh geometry per beat
    finally:
        kit.shutdown()


# ------------------------------------------------------------------- #
# Tools
# ------------------------------------------------------------------- #


def test_bench_check_device_family_skips_honestly():
    """No committed TPU round carries device_per_wake_ms yet: every
    DEVICE metric must SKIP (visible), never PASS silently."""
    rows = bench_check.check_family(str(REPO), "DEVICE")
    assert rows
    assert all(row["status"] == "SKIP" for row in rows)


def test_bench_check_device_family_gates_regression(tmp_path):
    prior = {"device_per_wake_ms": 10.0, "sweeps_mean": 5.0}
    newer = {"device_per_wake_ms": 30.0, "sweeps_mean": 5.0}
    (tmp_path / "BENCH_TPU_SESSION_r01.json").write_text(json.dumps(prior))
    (tmp_path / "BENCH_TPU_SESSION_r02.json").write_text(json.dumps(newer))
    rows = bench_check.check_family(str(tmp_path), "DEVICE")
    by_metric = {r["metric"]: r for r in rows}
    assert by_metric["device_per_wake_ms"]["status"] == "FAIL"
    assert by_metric["sweeps_mean"]["status"] == "PASS"


def test_uigc_top_device_panel_degrades():
    assert "device: -" in uigc_top.render_device_panel(None)[0]
    assert "device: -" in uigc_top.render_device_panel("not a doc")[0]
    obs = DeviceObservatory(node="x")
    try:
        lines = uigc_top.render_device_panel(obs.to_doc())
    finally:
        obs.close()
    assert lines[0].startswith("device: ledger")


def test_committed_device_figures_absent_on_cpu_trajectory(tmp_path):
    # the real repo: TPU sessions predate the chained-wake device figures
    assert device_report.committed_device_figures(str(REPO)) is None
    doc = {"device_per_wake_ms": 2.5, "sweeps_mean": 4.0}
    (tmp_path / "BENCH_WAKE_r01.json").write_text(json.dumps(doc))
    got = device_report.committed_device_figures(str(tmp_path))
    assert got["device_per_wake_ms"] == 2.5
    assert got["source"] == "BENCH_WAKE_r01.json"
    # families number rounds independently: a higher-numbered TPU
    # session must NOT outrank the canonical WAKE artifact
    (tmp_path / "BENCH_TPU_SESSION_r05.json").write_text(
        json.dumps({"device_per_wake_ms": 99.0})
    )
    got = device_report.committed_device_figures(str(tmp_path))
    assert got["source"] == "BENCH_WAKE_r01.json"


def test_replay_device_accepts_origin_tagged_events(tmp_path):
    """A real node's JSONL sink stamps every line with the node's
    origin; offline replay must fold them, not scope them away."""
    sink = tmp_path / "events.jsonl"
    lines = [
        {"event": events.COMPILE, "tag": "dec_wake", "geom": "g1",
         "hit": False, "origin": "uigc://node-a"},
        {"event": events.COMPILE, "tag": "dec_wake", "geom": "g1",
         "hit": True, "origin": "uigc://node-a"},
        {"event": events.HOST_TRANSFER, "site": "marks.decremental",
         "bytes": 512, "phase": "trace", "origin": "uigc://node-a"},
        {"event": events.DONATION_COPY, "site": "mesh.fold",
         "origin": "uigc://node-a"},
    ]
    sink.write_text("".join(json.dumps(line) + "\n" for line in lines))
    doc = uigc_top.replay_device(str(sink))
    assert doc is not None
    assert doc["compile"]["entries"] == [
        {"tag": "dec_wake", "geom": "g1", "hits": 1, "misses": 1,
         "compile_s": 0.0}
    ]
    assert doc["transfers"]["total_bytes"] == 512
    assert doc["donation"]["sites"] == {"mesh.fold": 1}
    assert doc["node"].startswith("replay:")


def test_ul011_flags_and_annotation(tmp_path):
    target = tmp_path / "engines" / "hot.py"
    target.parent.mkdir()
    target.write_text(
        "import numpy as np\n"
        "import jax\n"
        "def bad(self, x, y, z):\n"
        "    a = np.asarray(x)\n"                       # flagged
        "    b = jax.device_get(y)\n"                    # flagged
        "    c = z.item()\n"                             # flagged
        "    d = self._dev_flags.item()\n"               # flagged (attr recv)
        "    ok1 = np.asarray(x)  # readback: tested\n"  # annotated
        "    ok2 = np.asarray(x, dtype=np.int64)\n"      # dtype: host idiom
        "    return a, b, c, d, ok1, ok2\n"
    )
    violations = [
        v for v in uigc_lint.lint_paths([str(tmp_path)]) if v.rule == "UL011"
    ]
    assert len(violations) == 4
    lines = sorted(v.line for v in violations)
    assert lines == [4, 5, 6, 7]
    # outside engines/ops/parallel the rule never applies
    other = tmp_path / "models" / "cold.py"
    other.parent.mkdir()
    other.write_text("import numpy as np\nx = np.asarray([1])\n")
    assert not [
        v
        for v in uigc_lint.lint_paths([str(other)])
        if v.rule == "UL011"
    ]


def test_repo_is_ul011_strict_clean():
    violations = [
        v
        for v in uigc_lint.lint_paths([str(REPO / "uigc_tpu")])
        if v.rule == "UL011"
    ]
    budget = uigc_lint._load_allowlist(
        str(REPO / "tools" / "uigc_lint_allow.txt")
    )
    _grandfathered, fresh = uigc_lint.apply_allowlist(violations, budget)
    assert fresh == [], [v.render() for v in fresh]
