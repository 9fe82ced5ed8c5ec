"""Multi-device trace parity: the shard_map kernel must agree with the
single-host kernel on an 8-device virtual CPU mesh."""

import numpy as np
import pytest

from uigc_tpu.models import powerlaw_actor_graph, ring_graph
from uigc_tpu.ops import trace as trace_ops
from uigc_tpu.parallel import build_mesh, make_sharded_trace, shard_graph
from uigc_tpu.parallel.sharded_trace import Partition


@pytest.mark.parametrize(
    "graph",
    [
        powerlaw_actor_graph(4000, seed=3, garbage_fraction=0.4),
        ring_graph(n_rings=20, ring_size=13, live=False),
        ring_graph(n_rings=20, ring_size=13, live=True),
    ],
    ids=["powerlaw", "rings-garbage", "rings-live"],
)
def test_sharded_matches_host(graph):
    import jax

    n_devices = min(8, len(jax.devices()))
    mark_host = trace_ops.trace_marks_np(
        graph["flags"],
        graph["recv_count"],
        graph["supervisor"],
        graph["edge_src"],
        graph["edge_dst"],
        graph["edge_weight"],
    )

    packed = shard_graph(graph, n_devices)
    mesh = build_mesh(n_devices)
    traced = make_sharded_trace(mesh)
    mark_sharded = np.asarray(
        traced(
            packed["flags"],
            packed["recv_count"],
            packed["pair_src"],
            packed["pair_dst"],
        )
    )[: graph["flags"].shape[0]]

    assert np.array_equal(mark_host, mark_sharded)
    # And the generator's intended garbage is exactly the unmarked in-use set.
    in_use = (graph["flags"] & trace_ops.FLAG_IN_USE) != 0
    assert np.array_equal(in_use & ~mark_host, graph["expected_garbage"])


@pytest.mark.parametrize(
    "seed,mode",
    [(0, "push"), (1, "push"), (0, "pull"), (0, "jump"), (0, "auto")],
)
def test_sharded_pallas_matches_host(seed, mode):
    """The per-shard Pallas layout plane (packed base + insert buckets)
    must agree with the host oracle on the virtual mesh, under every
    propagation strategy (jump modes take the replicated jump-parent
    operand; pull modes skip saturated local supertiles)."""
    import jax

    from uigc_tpu.ops import pallas_incremental as pinc
    from uigc_tpu.parallel import make_sharded_pallas_trace, pack_shard_layouts

    n_devices = min(8, len(jax.devices()))
    s_rows = 8  # 1024-node supertiles: shards span several at this n
    rng = np.random.default_rng(seed)
    graph = powerlaw_actor_graph(20_000, seed=seed, garbage_fraction=0.4)
    n = graph["flags"].shape[0]
    mark_host = trace_ops.trace_marks_np(
        graph["flags"],
        graph["recv_count"],
        graph["supervisor"],
        graph["edge_src"],
        graph["edge_dst"],
        graph["edge_weight"],
    )

    super_sz = s_rows * 128
    chunk = n_devices * super_sz
    n_pad = ((n + chunk - 1) // chunk) * chunk
    flags = np.zeros(n_pad, np.uint8)
    flags[:n] = graph["flags"]
    recv = np.zeros(n_pad, np.int64)
    recv[:n] = graph["recv_count"]

    psrc, pdst, kinds = pinc.IncrementalPallasLayout.pairs_from_graph(
        graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
        graph["supervisor"],
    )
    # hold back a slice of pairs as "inserts" riding the bucket tier
    cut = psrc.size // 10
    order = rng.permutation(psrc.size)
    base_idx, ins_idx = order[cut:], order[:cut]

    stacked, meta, slot_vals = pack_shard_layouts(
        psrc[base_idx], pdst[base_idx], n_pad, n_devices, s_rows=s_rows
    )

    shard_size = meta["shard_size"]
    part = Partition(n_devices, super_sz)
    owner = part.owner(pdst[ins_idx])
    counts = np.bincount(owner, minlength=n_devices)
    m = max(64, int(counts.max(initial=1)))
    bsrc = np.full((n_devices, m), n_pad, np.int32)
    bdst = np.zeros((n_devices, m), np.int32)
    starts = np.zeros(n_devices, np.int64)
    starts[1:] = np.cumsum(counts)[:-1]
    so = np.argsort(owner, kind="stable")
    col = np.arange(ins_idx.size) - starts[owner[so]]
    bsrc[owner[so], col] = psrc[ins_idx][so]
    bdst[owner[so], col] = part.local(pdst[ins_idx][so])

    mesh = build_mesh(n_devices)
    traced = make_sharded_pallas_trace(
        mesh,
        meta["n_pad"],
        shard_size,
        meta["n_blocks"],
        meta["r_rows"],
        s_rows,
        m,
        sub=meta["sub"],
        group=meta["group"],
        mode=mode,
    )
    from uigc_tpu.ops import pallas_trace as pt

    jump = (
        (pt.jump_parents(psrc, pdst, n_pad),)
        if mode in (pt.MODE_JUMP, pt.MODE_AUTO)
        else ()
    )
    mark = np.asarray(
        traced(
            part.owner_major(flags),
            part.owner_major(recv),
            stacked["bmeta1"],
            stacked["bmeta2"],
            stacked["row_pos"],
            stacked["emeta"],
            bsrc,
            bdst,
            *jump,
        )
    )[:n]
    assert np.array_equal(mark, mark_host)


def test_sharded_auto_engages_where_one_device_does():
    """``auto`` means one thing in every program: on a chain the sharded
    trace engages the pointer jump on the same sweep as the
    single-device derivation of the same node space (both count the same
    replicated dirty chunks against the same price), finishes in the
    same number of sweeps, and gives the oracle's marks."""
    import jax

    from uigc_tpu.ops import pallas_decremental
    from uigc_tpu.ops import pallas_trace as pt
    from uigc_tpu.parallel import make_sharded_pallas_trace, pack_shard_layouts

    n_devices = min(8, len(jax.devices()))
    s_rows = 8
    n_pad = n_devices * 2 * s_rows * 128  # two supertiles a shard
    flags = np.full(n_pad, trace_ops.FLAG_IN_USE | trace_ops.FLAG_INTERNED, np.uint8)
    flags[0] |= trace_ops.FLAG_ROOT
    recv = np.zeros(n_pad, np.int64)
    psrc = np.arange(n_pad - 1, dtype=np.int64)
    pdst = psrc + 1
    jp = pt.jump_parents(psrc, pdst, n_pad)

    prep = pt.prepare_pairs(psrc, pdst, n_pad, s_rows=s_rows)
    one, one_stats = pallas_decremental.derive(
        flags, recv, [prep], mode="auto", jump_parent=jp
    )
    assert one.all()

    stacked, meta, _ = pack_shard_layouts(psrc, pdst, n_pad, n_devices, s_rows=s_rows)
    m = 64
    traced = make_sharded_pallas_trace(
        build_mesh(n_devices), n_pad, meta["shard_size"], meta["n_blocks"],
        meta["r_rows"], s_rows, m, sub=meta["sub"], group=meta["group"],
        mode="auto", with_stats=True,
    )
    part = Partition(n_devices, s_rows * 128)
    mark, stats = traced(
        part.owner_major(flags), part.owner_major(recv),
        stacked["bmeta1"], stacked["bmeta2"], stacked["row_pos"],
        stacked["emeta"], np.full((n_devices, m), n_pad, np.int32),
        np.zeros((n_devices, m), np.int32), jp,
    )
    assert np.asarray(mark).all()
    sweeps, jumped = int(stats["n_sweeps"]), int(stats["jump_sweeps"])
    assert 0 < jumped < sweeps  # engaged, and not from sweep 0
    # engagement is for good, so the first jump sweep is sweeps - jumped
    assert (sweeps, jumped) == (one_stats["n_sweeps"], one_stats["jump_sweeps"])


@pytest.mark.parametrize("mode", ["push", "auto"])
def test_sharded_decremental_wakes(mode):
    """The closure+repair wake on the virtual mesh: flag churn (halts,
    de-seeding, frees, slots coming alive) and bucket-tier edge churn
    across wakes, each diffed against the from-scratch host oracle.  A
    zeroed previous state is the cold start.  ``auto`` additionally
    exercises the replicated jump-parent operand maintained across
    wakes exactly as the mesh backend does (min-fold on insert,
    invalidate on delete)."""
    import jax

    from uigc_tpu.ops import pallas_incremental as pinc
    from uigc_tpu.ops import pallas_trace as pt
    from uigc_tpu.parallel import (
        make_sharded_decremental_wake,
        pack_shard_layouts,
    )

    n_devices = min(8, len(jax.devices()))
    s_rows = 8
    rng = np.random.default_rng(5)
    graph = powerlaw_actor_graph(20_000, seed=5, garbage_fraction=0.4)
    n = graph["flags"].shape[0]

    super_sz = s_rows * 128
    chunk = n_devices * super_sz
    n_pad = ((n + chunk - 1) // chunk) * chunk
    flags = np.zeros(n_pad, np.uint8)
    flags[:n] = graph["flags"]
    recv = np.zeros(n_pad, np.int64)
    recv[:n] = graph["recv_count"]

    psrc, pdst, kinds = pinc.IncrementalPallasLayout.pairs_from_graph(
        graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
        graph["supervisor"],
    )
    stacked, meta, slot_vals = pack_shard_layouts(
        psrc, pdst, n_pad, n_devices, s_rows=s_rows
    )
    shard_size = meta["shard_size"]
    part = Partition(n_devices, super_sz)
    m = 64  # bucket columns per shard
    bsrc = np.full((n_devices, m), n_pad, np.int32)
    bdst = np.zeros((n_devices, m), np.int32)
    bcount = np.zeros(n_devices, np.int64)

    wake = make_sharded_decremental_wake(
        mesh=build_mesh(n_devices),
        n_pad=n_pad,
        shard_size=shard_size,
        n_blocks=meta["n_blocks"],
        r_rows=meta["r_rows"],
        s_rows=s_rows,
        bucket_m=m,
        sub=meta["sub"],
        group=meta["group"],
        mode=mode,
    )
    use_jump = mode in (pt.MODE_JUMP, pt.MODE_AUTO)
    jp = pt.jump_parents(psrc, pdst, n_pad) if use_jump else None

    n_words = n_pad // 32
    zeros_w = np.zeros(n_words, np.int32)
    # mark, seed, halted, iu, active words; the last derivation's walks
    state = [zeros_w] * 5 + [np.zeros((), np.int32)]
    live_pairs = list(zip(psrc.tolist(), pdst.tolist()))
    bucket_pairs = []

    def oracle():
        allp = live_pairs + bucket_pairs
        s = np.array([p[0] for p in allp], np.int32)
        d = np.array([p[1] for p in allp], np.int32)
        return trace_ops.trace_marks_np(
            flags[:n], recv[:n], np.full(n, -1, np.int32),
            s, d, np.ones(len(allp), np.int64),
        )

    def words_of(ids):
        """The ids' bits where the wake takes them: in their owners' words."""
        w = np.zeros(n_words, np.uint32)
        ids = part.owner_major_index(np.asarray(sorted(set(ids)), np.int64), shard_size)
        if ids.size:
            np.bitwise_or.at(
                w, ids >> 5, np.uint32(1) << (ids & 31).astype(np.uint32)
            )
        return w.view(np.int32)

    def run_wake(del_ids, fresh_ids):
        nonlocal state
        out = wake(
            part.owner_major(flags), part.owner_major(recv),
            words_of(del_ids), words_of(fresh_ids),
            *state,
            stacked["bmeta1"], stacked["bmeta2"],
            stacked["row_pos"], stacked["emeta"],
            bsrc, bdst,
            *((jp,) if use_jump else ()),
        )
        state = [np.asarray(o) for o in out[:-1]]
        mark_w = part.slot_major(state[0], per=32).view(np.uint32)
        return np.unpackbits(mark_w.view(np.uint8), bitorder="little")[:n] > 0

    # cold start = full derivation
    assert np.array_equal(run_wake([], []), oracle())

    for wk in range(3):
        del_ids, fresh_ids = [], []
        # flag churn
        for _ in range(20):
            i = int(rng.integers(0, n))
            r = rng.random()
            if r < 0.3:
                flags[i] |= trace_ops.FLAG_HALTED
            elif r < 0.5:
                flags[i] ^= trace_ops.FLAG_BUSY
            elif r < 0.7:
                recv[i] = 0 if recv[i] else 2
            elif r < 0.85:
                flags[i] = 0  # freed
            else:
                flags[i] = trace_ops.FLAG_IN_USE | trace_ops.FLAG_INTERNED
        # bucket-tier inserts (fresh pairs)
        for _ in range(10):
            s_, d_ = int(rng.integers(0, n)), int(rng.integers(0, n))
            sh = int(part.owner(d_))
            c = int(bcount[sh])
            if c >= m or (s_, d_) in bucket_pairs:
                continue
            bsrc[sh, c] = s_
            bdst[sh, c] = part.local(d_)
            bcount[sh] = c + 1
            bucket_pairs.append((s_, d_))
            fresh_ids.append(d_)
            if use_jump and s_ < jp[d_]:  # min-fold, as the mesh backend
                jp[d_] = s_
        # base-layout deletions via in-place slot masking
        for _ in range(10):
            j = int(rng.integers(0, len(live_pairs)))
            if live_pairs[j] is None:
                continue
            s_, d_ = live_pairs[j]
            live_pairs[j] = None
            if use_jump and jp[d_] == s_:  # invalidate, as the mesh backend
                jp[d_] = n_pad
            sv = int(slot_vals[j])
            sh, ri, col = sv >> 40, (sv >> 8) & 0xFFFFFFFF, sv & 0xFF
            stacked["row_pos"][sh, ri, col] = pt._PAD_ROW
            stacked["emeta"][sh, ri, col] = 0
            del_ids.append(d_)
        # live_pairs keeps None holes so slot_vals indices stay stable
        live_pairs_c = [p for p in live_pairs if p is not None]

        got = run_wake(del_ids, fresh_ids)
        allp = live_pairs_c + bucket_pairs
        s = np.array([p[0] for p in allp], np.int32)
        d = np.array([p[1] for p in allp], np.int32)
        expected = trace_ops.trace_marks_np(
            flags[:n], recv[:n], np.full(n, -1, np.int32),
            s, d, np.ones(len(allp), np.int64),
        )
        assert np.array_equal(got, expected), (
            f"wake {wk}: {int((got != expected).sum())} mismatches"
        )
