"""Differential test: Pallas-scatter trace vs the numpy oracle.

Random graphs with all the semantic wrinkles — halted nodes, roots,
negative/zero-weight edges, supervisor pointers, free slots — must produce
identical mark vectors (the reference author's dual-graph technique,
reference: ShadowGraph.java:176-199).  The kernel is reached through the
one program a chip runs it in, the decremental wake deriving from nothing
(``pallas_decremental.derive``).  On CPU the kernel runs in Pallas
interpret mode; on TPU it compiles for real.
"""

import numpy as np
import pytest

from uigc_tpu.ops import pallas_decremental, pallas_trace, trace as trace_ops

F = trace_ops


def derive_graph(flags, recv, supervisor, edge_src, edge_dst, edge_weight,
                 mode="push"):
    """Marks from nothing over one full pack of the graph's pairs."""
    n = flags.shape[0]
    prep = pallas_trace.prepare_chunks(
        edge_src, edge_dst, edge_weight, supervisor, n
    )
    jp = None
    if mode in (pallas_trace.MODE_JUMP, pallas_trace.MODE_AUTO):
        jp = pallas_trace.jump_parents_from_graph(
            edge_src, edge_dst, edge_weight, supervisor, n
        )
    marks, _ = pallas_decremental.derive(
        flags, recv, [prep], mode=mode, jump_parent=jp
    )
    return marks


def random_graph(rng, n, n_edges):
    flags = np.zeros(n, dtype=np.uint8)
    in_use = rng.random(n) < 0.9
    flags[in_use] |= F.FLAG_IN_USE
    flags[rng.random(n) < 0.8] |= F.FLAG_INTERNED
    flags[rng.random(n) < 0.1] |= F.FLAG_BUSY
    flags[rng.random(n) < 0.05] |= F.FLAG_ROOT
    flags[rng.random(n) < 0.1] |= F.FLAG_HALTED
    flags[rng.random(n) < 0.7] |= F.FLAG_LOCAL

    recv = np.zeros(n, dtype=np.int64)
    recv[rng.random(n) < 0.15] = rng.integers(-3, 10)

    supervisor = np.full(n, -1, dtype=np.int32)
    sup_mask = rng.random(n) < 0.4
    supervisor[sup_mask] = rng.integers(0, n, size=int(sup_mask.sum()))

    edge_src = rng.integers(0, n, size=n_edges).astype(np.int32)
    edge_dst = rng.integers(0, n, size=n_edges).astype(np.int32)
    edge_weight = rng.integers(-2, 5, size=n_edges).astype(np.int64)
    return flags, recv, supervisor, edge_src, edge_dst, edge_weight


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("n,n_edges", [(50, 120), (300, 900), (1000, 4000)])
def test_pallas_matches_oracle(seed, n, n_edges):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n, n_edges)
    expected = trace_ops.trace_marks_np(*g)
    got = derive_graph(*g)
    assert np.array_equal(got, expected)


@pytest.mark.parametrize("mode", ["push", "pull", "jump", "auto"])
@pytest.mark.parametrize("seed", [0, 1])
def test_trace_modes_match_oracle(seed, mode):
    """Every propagation strategy (uigc.crgc.trace-mode) must produce
    oracle-identical marks over graphs with all the semantic wrinkles —
    the direction-optimizing gates and the pointer jumps are
    accelerations, never semantics."""
    rng = np.random.default_rng(seed)
    g = random_graph(rng, 1500, 6000)
    expected = trace_ops.trace_marks_np(*g)
    got = derive_graph(*g, mode=mode)
    assert np.array_equal(got, expected)


def test_jump_collapses_chain_sweeps():
    """The ISSUE-6 acceptance shape: on a long chain (diameter = n) the
    push fixpoint needs O(n) sweeps while pointer-jumping converges in
    O(log n) — and both agree with the oracle.  Sweep counts are the
    wake's own counters, which is what the wake profiler reports."""
    n = 200
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, dtype=np.uint8)
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, dtype=np.int64)
    sup = np.full(n, -1, dtype=np.int32)
    src = np.arange(n - 1, dtype=np.int32)
    dst = np.arange(1, n, dtype=np.int32)
    w = np.ones(n - 1, dtype=np.int64)
    expected = trace_ops.trace_marks_np(flags, recv, sup, src, dst, w)
    prep = pallas_trace.prepare_chunks(src, dst, w, sup, n)
    jp = pallas_trace.jump_parents_from_graph(src, dst, w, sup, n)

    push_marks, push_stats = pallas_decremental.derive(
        flags, recv, [prep], mode="push"
    )
    jump_marks, jump_stats = pallas_decremental.derive(
        flags, recv, [prep], mode="jump", jump_parent=jp
    )
    assert np.array_equal(push_marks, expected)
    assert np.array_equal(jump_marks, expected)
    push_sweeps = push_stats["n_sweeps"]
    jump_sweeps = jump_stats["n_sweeps"]
    assert push_sweeps >= n - 1  # O(diameter)
    assert jump_sweeps <= 10  # O(log diameter) at JUMP_STEPS=2
    assert jump_sweeps * 6 < push_sweeps


def test_mode_sweep_counts_at_powerlaw_geometry():
    """At the benchmark graph model (powerlaw, the 10M-actor geometry's
    shape at reduced n — sweep counts are hardware-independent and only
    weakly size-dependent) ``jump`` must converge in <=6 sweeps where
    push needs more, and ``auto`` must NOT pay for it: on the v5e a jump
    sweep costs nine push sweeps (PERF.md section 6, PR 28), the graph
    is shallow, so auto runs push's sweeps and never engages the jump.
    (Until PR 28 this asserted ``auto <= 6`` and ``auto < push``: the
    CPU-era claim that a sweep is dear and a gather cheap.)  Four walk
    chunks, so that the dense middle of the fixpoint shows as dense: in
    a one-chunk layout every sweep looks sparse."""
    from uigc_tpu.models.graphgen import powerlaw_actor_graph

    n = 1 << 17
    g = powerlaw_actor_graph(n, seed=0, garbage_fraction=0.5)
    prep = pallas_trace.prepare_chunks(
        g["edge_src"].astype(np.int32),
        g["edge_dst"].astype(np.int32),
        g["edge_weight"],
        g["supervisor"],
        n,
    )
    jp = pallas_trace.jump_parents_from_graph(
        g["edge_src"], g["edge_dst"], g["edge_weight"], g["supervisor"], n
    )
    expected = trace_ops.trace_marks_np(
        g["flags"], g["recv_count"], g["supervisor"],
        g["edge_src"], g["edge_dst"], g["edge_weight"],
    )
    sweeps, jumps = {}, {}
    for mode in ("push", "auto", "jump"):
        marks, stats = pallas_decremental.derive(
            g["flags"], g["recv_count"], [prep], mode=mode,
            jump_parent=None if mode == "push" else jp,
        )
        assert np.array_equal(marks, expected), mode
        sweeps[mode] = stats["n_sweeps"]
        jumps[mode] = stats["jump_sweeps"]
    assert sweeps["jump"] <= 6
    assert sweeps["jump"] < sweeps["push"]
    assert sweeps["auto"] == sweeps["push"]
    assert jumps == {"push": 0, "auto": 0, "jump": sweeps["jump"]}


def chain_graph(n):
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, dtype=np.uint8)
    flags[0] |= F.FLAG_ROOT
    src = np.arange(n - 1, dtype=np.int32)
    return (flags, np.zeros(n, dtype=np.int64),
            np.full(n, -1, dtype=np.int32), src, src + 1,
            np.ones(n - 1, dtype=np.int64))


def auto_price(prep):
    """AUTO's price of a jump sweep for a one-layout trace, from the same
    helper and the same arguments as the wake fn takes it."""
    n_chunks = prep["r_rows"] // (pallas_trace.ROWS * prep["group"])
    pull_cut = max(1, round(pallas_trace.DEFAULT_PULL_DENSITY * n_chunks))
    return pallas_trace.auto_jump_policy(
        prep["n"],
        pallas_trace.kernel_slots((pallas_trace.layout_spec(prep),)),
        n_chunks, pull_cut,
    ).price


@pytest.mark.parametrize("n", [1000, 5000])
def test_auto_engages_the_jump_on_a_chain(n):
    """The other side of laziness: on a chain (diameter = n, one walk
    chunk dirty per sweep) ``auto`` stays sparse sweep after sweep, so
    it engages the jump once it has walked the price of one jump sweep,
    keeps it engaged, and finishes in O(price + log n) sweeps where push
    needs n - 1 — with the oracle's marks."""
    flags, recv, sup, src, dst, w = chain_graph(n)
    expected = trace_ops.trace_marks_np(flags, recv, sup, src, dst, w)
    assert expected.all()
    prep = pallas_trace.prepare_chunks(src, dst, w, sup, n)
    jp = pallas_trace.jump_parents_from_graph(src, dst, w, sup, n)
    price = auto_price(prep)
    assert 1 < price < n // 8  # long enough to cross it, far under n
    marks, stats = pallas_decremental.derive(
        flags, recv, [prep], mode="auto", jump_parent=jp
    )
    assert np.array_equal(marks, expected)
    k = stats["n_sweeps"]
    assert k <= pallas_trace.MAX_SWEEP_STATS  # every sweep has its slot
    # one chunk walked per sweep: engaged exactly when the price is paid,
    # and from then on to the end
    assert stats["jump_on"] == [0] * price + [1] * (k - price)
    assert stats["jump_sweeps"] == k - price > 0
    # 4^k reach per engaged sweep (JUMP_STEPS = 2), plus the sweep that
    # finds nothing new
    assert k - price <= np.log2(n) / 2 + 3
    assert k * 8 < n - 1


def test_no_edges():
    n = 40
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, dtype=np.uint8)
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, dtype=np.int64)
    sup = np.full(n, -1, dtype=np.int32)
    e = np.zeros(0, dtype=np.int32)
    w = np.zeros(0, dtype=np.int64)
    expected = trace_ops.trace_marks_np(flags, recv, sup, e, e, w)
    got = derive_graph(flags, recv, sup, e, e, w)
    assert np.array_equal(got, expected)


def test_long_chain():
    # A chain forces many fixpoint iterations (diameter = n).
    n = 300
    flags = np.full(n, F.FLAG_IN_USE | F.FLAG_INTERNED, dtype=np.uint8)
    flags[0] |= F.FLAG_ROOT
    recv = np.zeros(n, dtype=np.int64)
    sup = np.full(n, -1, dtype=np.int32)
    src = np.arange(n - 1, dtype=np.int32)
    dst = np.arange(1, n, dtype=np.int32)
    w = np.ones(n - 1, dtype=np.int64)
    expected = trace_ops.trace_marks_np(flags, recv, sup, src, dst, w)
    assert expected.all()
    got = derive_graph(flags, recv, sup, src, dst, w)
    assert np.array_equal(got, expected)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("sub,group", [(4, 8), (2, 2), (4, 1), (1, 8)])
def test_wide_geometry_matches_oracle(seed, sub, group):
    """The TPU walk geometry (sub-blocks per grid step, chunks per walk
    iteration) packs and propagates identically to the minimal interpret
    geometry — covered here in interpret mode so a packer/kernel
    geometry bug is caught off-chip too (the compiled tier re-checks the
    wide pair on hardware)."""
    rng = np.random.default_rng(seed)
    flags, recv, supervisor, edge_src, edge_dst, edge_weight = random_graph(
        rng, 2000, 8000
    )
    expected = trace_ops.trace_marks_np(
        flags, recv, supervisor, edge_src, edge_dst, edge_weight
    )
    prep = pallas_trace.prepare_chunks(
        edge_src, edge_dst, edge_weight, supervisor, flags.shape[0],
        s_rows=8, sub=sub, group=group,
    )
    got, _ = pallas_decremental.derive(flags, recv, [prep])
    assert np.array_equal(got, expected)


# --------------------------------------------------------------------- #
# The compacted grid: a launch visits only the blocks that have work
# --------------------------------------------------------------------- #

GRID_N = 70_000  # three walk chunks of 32,768 actors at group 1
GRID_S_ROWS = 8  # 69 supertiles of 1,024 actors
GRID_CHUNK_ROWS = pallas_trace.ROWS  # table rows a walk chunk has at group 1


def _grid_layout(compact):
    """A packed layout whose tiles have several blocks each, and the pairs
    it holds.  The compact one touches every fourth supertile only."""
    rng = np.random.default_rng(32)
    m = 150_000
    psrc = rng.integers(0, GRID_N, m)
    pdst = rng.integers(0, GRID_N, m)
    if compact:
        keep = (pdst // (GRID_S_ROWS * pallas_trace.LANE)) % 4 == 1
        psrc, pdst = psrc[keep], pdst[keep]
    prep = pallas_trace.prepare_pairs(
        psrc, pdst, GRID_N, s_rows=GRID_S_ROWS, pad_blocks_pow2=True,
        compact_supers=compact, sub=1, group=1,
    )
    return prep, psrc, pdst


def _block_iters(prep, dirty, gate):
    """Chunk-iterations per block, by the kernel's rule, in numpy."""
    c_lo = prep["bmeta2"] >> pallas_trace._SPAN_BITS
    span = prep["bmeta2"] & ((1 << pallas_trace._SPAN_BITS) - 1)
    d = np.concatenate([[0], np.cumsum(dirty)])
    g = gate[prep["bmeta1"] >> 1]
    return np.where(
        g == pallas_trace.GATE_SKIP, 0,
        np.where(g == pallas_trace.GATE_FULL, span, d[c_lo + span] - d[c_lo]),
    )


def _bits(table, node):
    """The table's bits at these node ids."""
    word = node >> 5
    return (table[word >> 7, word & 127] >> (node & 31)) & 1


def _reference_contribs(prep, psrc, pdst, table, dirty, gate, new=None,
                        chunk_rows=GRID_CHUNK_ROWS):
    """The uncompacted reference, which contracts whatever it gathered: a
    segment-sum over the layout's pairs of the source's bit, for the pairs
    whose source chunk the sweep walks into their destination tile (dirty,
    or the tile forced; never a skipped tile).  The bit is the table's in
    a forced tile and ``new``'s (the table's own where none is given) in
    every other."""
    super_sz = prep["s_rows"] * pallas_trace.LANE
    tile = pdst // super_sz
    if "super_ids" in prep:  # compact: global supertile -> layout tile
        tile = np.searchsorted(prep["super_ids"][: len(np.unique(tile))], tile)
    chunk = (psrc >> 12) // chunk_rows
    g = gate[tile]
    bit = np.where(
        g == pallas_trace.GATE_FULL, _bits(table, psrc),
        _bits(table if new is None else new, psrc),
    )
    walked = np.where(
        g == pallas_trace.GATE_SKIP, False,
        (g == pallas_trace.GATE_FULL) | dirty[chunk],
    )
    out_tiles = prep.get("out_supers", prep["n_super"])
    out = np.zeros(out_tiles * super_sz, np.float32)
    np.add.at(out, tile * super_sz + pdst % super_sz, (bit * walked).astype(np.float32))
    return out.reshape(-1, pallas_trace.LANE)


def _gathering_blocks(prep, table, dirty, gate, new=None,
                      chunk_rows=GRID_CHUNK_ROWS):
    """Per block, whether the kernel's walk gathers a set bit this sweep,
    from the packed layout alone: a slot's source is decoded from
    ``row_pos`` and ``emeta``, it is walked if its chunk is (the block's
    tile forced, or the chunk dirty and the tile not skipped), and a
    forced block reads the table, every other ``new``."""
    src = pallas_trace.slot_sources(prep, -1)
    held = src >= 0
    src = np.where(held, src, 0)
    g = gate[prep["bmeta1"] >> 1][:, None]
    chunk = (src >> 12) // chunk_rows  # 2^12 nodes a table row
    walked = held & (g != pallas_trace.GATE_SKIP) & (
        (g == pallas_trace.GATE_FULL) | dirty[chunk]
    )
    bit = np.where(
        g == pallas_trace.GATE_FULL, _bits(table, src),
        _bits(table if new is None else new, src),
    )
    return (walked & (bit > 0)).any(axis=1)


def _launch(prep, table, dirty, gate, fill=None, new=None, interpret=True,
            group=1, dst_gate=True):
    """(contributions, steps, steps that contracted) of one launch; over a
    buffer of ``fill``.  The kernel's table operand is ``table`` over
    ``new``, the table's own bits where none is given.  Without
    ``dst_gate`` the kernel has no gate operand (``gate`` is zeros)."""
    import jax
    import jax.numpy as jnp

    n_chunks = dirty.size
    d = np.concatenate([[0], np.cumsum(dirty)]).astype(np.int32)
    l = np.zeros(n_chunks, np.int32)
    l[d[:-1][dirty]] = np.flatnonzero(dirty)
    propagate = pallas_trace.build_propagate(
        prep["n_blocks"], prep.get("out_supers", prep["n_super"]),
        prep["r_rows"], prep["s_rows"], interpret, sub=prep["sub"],
        group=group, dst_gate=dst_gate,
    )
    tables = np.concatenate([table, table if new is None else new])
    operands = (d, l, *((gate,) if dst_gate else ()), prep["bmeta1"],
                prep["bmeta2"], tables, prep["row_pos"], prep["emeta"])
    if fill is None:
        out, steps, contracted, walks, trips = jax.jit(propagate.with_steps)(*operands)
    else:
        plane = jnp.full(
            (propagate(*operands).shape[0], pallas_trace.LANE), fill, jnp.float32
        )
        out, steps, contracted, walks, trips = jax.jit(propagate.onto)(plane, *operands)
    n_iter = _block_iters(prep, dirty, gate)
    assert int(walks) == int(n_iter.sum())
    assert int(trips) == int(((n_iter + 1) // 2).sum())  # two chunks a trip
    return np.asarray(out), int(steps), int(contracted)


@pytest.mark.parametrize(
    "case",
    [
        "nothing_dirty", "one_dirty_chunk", "all_dirty",
        "forced_tile_clean_chunks", "skipped_tile_dirty_sources",
        "first_static_block_inactive", "compact_unvisited_tile",
        "padding_blocks_only",
    ],
)
def test_compacted_grid_equals_uncompacted_reference(case):
    """The kernel's contributions over a grid as long as the list of active
    blocks equal the segment-sum over the same pairs, whatever the list
    leaves out.  A tile it never visits keeps what the output buffer held,
    so over the zero plane it reads as exactly zero; a tile's first ACTIVE
    block, wherever it stands among the tile's blocks, overwrites what the
    buffer held there, so over a buffer of sevens a visited tile reads as
    over zeros (a block that wrongly accumulated would read seven more)."""
    compact = case == "compact_unvisited_tile"
    prep, psrc, pdst = _grid_layout(compact)
    if case == "padding_blocks_only":  # no pair: dummy and padding blocks
        psrc = pdst = np.zeros(0, np.int64)
        prep = pallas_trace.prepare_pairs(
            psrc, pdst, GRID_N, s_rows=GRID_S_ROWS, pad_blocks_pow2=True,
            sub=1, group=1,
        )
    n_chunks = prep["r_rows"] // GRID_CHUNK_ROWS
    assert n_chunks == 3
    out_tiles = prep.get("out_supers", prep["n_super"])
    rng = np.random.default_rng(7)
    table = rng.integers(0, 1 << 31, (prep["r_rows"], pallas_trace.LANE)).astype(np.int32)
    dirty = np.zeros(n_chunks, bool)
    gate = np.zeros(out_tiles, np.int32)
    if case in ("one_dirty_chunk", "compact_unvisited_tile"):
        dirty[1] = True
    elif case in ("all_dirty", "skipped_tile_dirty_sources"):
        dirty[:] = True
    elif case == "first_static_block_inactive":
        dirty[n_chunks - 1] = True
    elif case == "padding_blocks_only":
        dirty[:] = True
        gate[:] = pallas_trace.GATE_FULL
    if case == "forced_tile_clean_chunks":
        gate[[3, out_tiles - 1]] = pallas_trace.GATE_FULL
    if case == "skipped_tile_dirty_sources":
        gate[[0, 5]] = pallas_trace.GATE_SKIP
    if compact:  # one touched tile is saturated: the list never visits it
        gate[2] = pallas_trace.GATE_SKIP

    n_iter = _block_iters(prep, dirty, gate)
    block_tile = prep["bmeta1"] >> 1
    visited = np.zeros(out_tiles, bool)
    visited[block_tile[n_iter > 0]] = True
    if case == "first_static_block_inactive":
        first = (prep["bmeta1"] & 1) == 1
        late = visited[block_tile] & first & (n_iter == 0)
        assert late.sum() > 10  # tiles whose first block has no work
    if case in ("one_dirty_chunk", "all_dirty"):
        assert visited.all() and (n_iter == 0).any()  # padding never runs
    if compact:
        assert not visited[2] and visited.sum() == out_tiles - 1 - (
            out_tiles - len(np.unique(pdst // (GRID_S_ROWS * pallas_trace.LANE)))
        )

    out, steps, contracted = _launch(prep, table, dirty, gate)
    assert steps == int((n_iter > 0).sum())
    assert contracted == int(_gathering_blocks(prep, table, dirty, gate).sum())
    if case in ("nothing_dirty", "padding_blocks_only"):
        assert steps == contracted == 0
    expected = _reference_contribs(prep, psrc, pdst, table, dirty, gate)
    assert np.array_equal(out, expected)  # NaN anywhere would fail this
    rows = prep["s_rows"]
    unvisited = np.repeat(~visited, rows)
    assert not out[unvisited].any() and (steps == 0 or out[~unvisited].any())
    # over a buffer deliberately filled with non-zeros
    if steps == 0:  # the one step of an empty launch zeroes the last block's tile
        visited[block_tile[-1]] = True
        unvisited = np.repeat(~visited, rows)
    out7, steps7, contracted7 = _launch(prep, table, dirty, gate, fill=7.0)
    assert (steps7, contracted7) == (steps, contracted)
    assert np.array_equal(out7, np.where(unvisited[:, None], np.float32(7), expected))


def _set_bits(nodes, r_rows):
    """A word table with exactly these nodes' bits set."""
    flat = np.zeros(r_rows * pallas_trace.LANE, np.int64)
    np.bitwise_or.at(flat, nodes >> 5, np.int64(1) << (nodes & 31))
    return flat.astype(np.uint32).view(np.int32).reshape(r_rows, pallas_trace.LANE)


@pytest.mark.parametrize("compact", [False, True], ids=["dense", "compact"])
@pytest.mark.parametrize(
    "case",
    ["few_new_bits", "first_block_gathers_nothing", "a_tile_gathers_nothing",
     "nothing_new"],
)
def test_a_block_contracts_only_when_it_gathered_a_bit(case, compact):
    """A walked block whose gather found nothing skips its contraction, and
    the contributions stay those of the formulation that contracts
    whatever it gathered, bit for bit: in tiles under ``GATE_PUSH`` (the
    NEW bits of the dirty chunks), ``GATE_FULL`` (the full table over the
    full span) and ``GATE_SKIP``.  A skipped block that is the first
    active one of its tile still zeroes the tile (over a buffer of sevens
    it would read seven otherwise), one that is not touches nothing, and
    the kernel's count of the steps that contracted is the count of the
    blocks that gathered a bit, taken from the packed layout in numpy."""
    prep, psrc, pdst = _grid_layout(compact)
    n_chunks = prep["r_rows"] // GRID_CHUNK_ROWS
    out_tiles = prep.get("out_supers", prep["n_super"])
    rng = np.random.default_rng(39)
    table = rng.integers(0, 1 << 31, (prep["r_rows"], pallas_trace.LANE)).astype(np.int32)
    dirty = np.ones(n_chunks, bool)
    gate = np.zeros(out_tiles, np.int32)
    # a compact layout pads its tiles: the last that holds a pair
    forced = [3, len(np.unique(pdst // (GRID_S_ROWS * pallas_trace.LANE))) - 1]
    gate[forced] = pallas_trace.GATE_FULL
    gate[[0, 5]] = pallas_trace.GATE_SKIP
    block_tile = prep["bmeta1"] >> 1
    n_iter = _block_iters(prep, dirty, gate)
    set_src = np.unique(psrc[_bits(table, psrc) > 0])
    if case == "few_new_bits":
        dirty[1] = False
        n_iter = _block_iters(prep, dirty, gate)
        new = _set_bits(rng.choice(set_src, 60, replace=False), prep["r_rows"])
    elif case == "first_block_gathers_nothing":
        # the sources of each tile's LAST active block alone are new
        active = np.flatnonzero(n_iter > 0)
        last = active[np.r_[block_tile[active][1:] != block_tile[active][:-1], True]]
        src = pallas_trace.slot_sources(prep, -1)[last]
        new = _set_bits(np.intersect1d(src, set_src)[::7], prep["r_rows"])
    elif case == "a_tile_gathers_nothing":
        super_sz = prep["s_rows"] * pallas_trace.LANE
        tile = pdst // super_sz
        if compact:
            tile = np.searchsorted(prep["super_ids"][: len(np.unique(tile))], tile)
        quiet = np.isin(tile, [2, 9])
        new = table & ~_set_bits(np.unique(psrc[quiet]), prep["r_rows"])
    else:
        new = np.zeros_like(table)
    assert not (new & ~table).any()  # what is new is set

    gathers = _gathering_blocks(prep, table, dirty, gate, new)
    assert not gathers[n_iter == 0].any()
    visited = np.zeros(out_tiles, bool)
    visited[block_tile[n_iter > 0]] = True
    tile_gathers = np.zeros(out_tiles, bool)
    tile_gathers[block_tile[gathers]] = True
    if case == "first_block_gathers_nothing":
        active = np.flatnonzero(n_iter > 0)
        first = active[np.r_[True, block_tile[active][1:] != block_tile[active][:-1]]]
        late = tile_gathers[block_tile[first]] & ~gathers[first]
        assert late.sum() > 10  # a later block of the tile gathers, its first not
    if case == "a_tile_gathers_nothing":
        assert visited[[2, 9]].all() and not tile_gathers[[2, 9]].any()
    if case == "nothing_new":  # the forced tiles alone read the table
        assert sorted(np.flatnonzero(tile_gathers)) == forced
    if case == "few_new_bits":
        assert 0 < gathers.sum() < (n_iter > 0).sum() // 2

    expected = _reference_contribs(prep, psrc, pdst, table, dirty, gate, new)
    assert expected[np.repeat(tile_gathers, prep["s_rows"])].any()
    out, steps, contracted = _launch(prep, table, dirty, gate, new=new)
    assert steps == int((n_iter > 0).sum())
    assert contracted == int(gathers.sum()) <= steps
    assert np.array_equal(out, expected)
    # a visited tile reads as over zeros whatever the buffer held
    out7, steps7, contracted7 = _launch(prep, table, dirty, gate, fill=7.0, new=new)
    assert (steps7, contracted7) == (steps, contracted)
    unvisited = np.repeat(~visited, prep["s_rows"])
    assert np.array_equal(out7, np.where(unvisited[:, None], np.float32(7), expected))


def test_unvisited_tiles_of_a_compact_layout_add_nothing():
    """Through the sweep both fixpoints share: a compact layout's output is
    scattered into the global plane by ``super_ids``, so a tile it never
    visited must add zeros there and not what its buffer held."""
    import jax
    import jax.numpy as jnp

    dense, psrc_d, pdst_d = _grid_layout(False)
    comp, psrc_c, pdst_c = _grid_layout(True)
    specs = (pallas_trace.layout_spec(dense), pallas_trace.layout_spec(comp))
    props = pallas_trace.build_layout_propagates(
        specs, dense["n_super"], dense["r_rows"], GRID_S_ROWS, True, dst_gate=True
    )
    sweep = pallas_trace.build_sweep_contribs(
        specs, props, GRID_N, dense["n_super"], GRID_S_ROWS, jnp
    )
    rng = np.random.default_rng(9)
    table = rng.integers(0, 1 << 31, (dense["r_rows"], pallas_trace.LANE)).astype(np.int32)
    dirty = np.array([False, True, False])
    gate = np.zeros(dense["n_super"], np.int32)
    gate[::3] = pallas_trace.GATE_SKIP
    d = np.concatenate([[0], np.cumsum(dirty)]).astype(np.int32)
    l = np.array([1, 0, 0], np.int32)
    args = pallas_trace.device_args(dense) + pallas_trace.device_args(comp)
    tables = np.concatenate([table, table])
    hits, steps, contracted, walks, trips = jax.jit(
        lambda t, d, l, g, *a: sweep.with_steps(t, d, l, a, gate=g)
    )(tables, d, l, gate, *args)
    expected = (
        _reference_contribs(dense, psrc_d, pdst_d, table, dirty, gate)
        + _reference_contribs(
            {k: v for k, v in comp.items() if k not in ("super_ids", "out_supers")},
            psrc_c, pdst_c, table, dirty, gate,
        )
    ) > 0
    assert np.array_equal(np.asarray(hits), expected)
    n_iter_c = _block_iters(comp, dirty, gate[comp["super_ids"]])
    assert int(steps) == int(
        (_block_iters(dense, dirty, gate) > 0).sum() + (n_iter_c > 0).sum()
    )
    assert int(contracted) == int(
        _gathering_blocks(dense, table, dirty, gate).sum()
        + _gathering_blocks(comp, table, dirty, gate[comp["super_ids"]]).sum()
    )
    n_iter_d = _block_iters(dense, dirty, gate)
    assert int(walks) == int(n_iter_d.sum() + n_iter_c.sum())
    assert int(trips) == int(((n_iter_d + 1) // 2).sum() + ((n_iter_c + 1) // 2).sum())
    assert np.array_equal(
        np.asarray(jax.jit(lambda t, d, l, g, *a: sweep(t, d, l, a, gate=g))(
            tables, d, l, gate, *args)),
        expected,
    )


# --------------------------------------------------------------------- #
# The contraction: one-hots written as packed bf16 words, over lanes
# --------------------------------------------------------------------- #

WORDS_N = 8 * 4096  # one walk chunk of sources, eight tiles of 32 x 128 cells
WORDS_CASES = [
    "every_cell_its_own_count", "a_block_on_one_cell",
    "set_beside_clear_same_cell", "second_block_accumulates",
]


def _words_case(case, sub):
    """(prep, psrc, pdst, table, tile, per-cell sums expected in that tile)
    of a hand-made layout whose pairs all end in one 32 x 128 tile.  The
    sums tell a wrong order of the rows inside the operands' packed words
    apart: ``pltpu.bitcast`` puts bf16 rows 2i and 2i + 1 into word row i,
    and the kernel writes its one-hots as those words."""
    rng = np.random.default_rng(41)
    lane = pallas_trace.LANE
    s_rows = pallas_trace.S_ROWS
    table = rng.integers(0, 1 << 31, (8, lane)).astype(np.int32)
    is_set = _bits(table, np.arange(WORDS_N)) > 0
    by_row = [np.flatnonzero(is_set[r * 4096 : (r + 1) * 4096]) + r * 4096
              for r in range(8)]
    clear = np.flatnonzero(~is_set)
    slots = pallas_trace.ROWS * sub * lane  # a block's
    want = np.zeros((s_rows, lane), np.int64)
    if case == "every_cell_its_own_count":
        # injective along every row and every column of the tile (131 is
        # prime), so no permutation of rows, of lanes or of both reads as
        # the identity: both halves of every word of both operands tell
        tile = 1
        si, li = np.mgrid[:s_rows, :lane]
        want = 1 + (37 * si + li) % 131
        cell = np.repeat(np.arange(s_rows * lane), want.reshape(-1))
        psrc = rng.choice(np.flatnonzero(is_set), cell.size)
    elif case in ("a_block_on_one_cell", "second_block_accumulates"):
        # 128 * sub set sources in each of the eight row classes fill a
        # block: its largest sum, and twice that over two blocks
        tile = 2
        blocks = 2 if case == "second_block_accumulates" else 1
        psrc = np.concatenate(
            [rng.choice(rows, blocks * slots // 8, replace=False)
             for rows in by_row]
        )
        cell = np.full(psrc.size, (s_rows - 1) * lane + lane - 1)  # odd, odd
        want[-1, -1] = blocks * slots
    else:
        # the gathered bit counts, not the slot: every cell of a diagonal
        # band holds set and clear sources, and reads its set ones alone
        tile = 3
        cells = np.arange(s_rows * lane)[:: lane // 2 + 1]
        n_set = 1 + cells % 3
        cell = np.concatenate([np.repeat(cells, n_set), np.repeat(cells, 2)])
        psrc = np.concatenate(
            [rng.choice(np.flatnonzero(is_set), n_set.sum()),
             rng.choice(clear, 2 * cells.size)]
        )
        want.reshape(-1)[cells] = n_set
    pdst = tile * s_rows * lane + cell
    prep = pallas_trace.prepare_pairs(
        psrc, pdst, WORDS_N, s_rows=s_rows, pad_blocks_pow2=True, sub=sub,
        group=1,
    )
    return prep, psrc, pdst, table, tile, want


def _check_words_case(case, sub, interpret):
    prep, psrc, pdst, table, tile, want = _words_case(case, sub)
    s_rows, slots = prep["s_rows"], prep["row_pos"].size // prep["n_blocks"]
    dirty = np.ones(1, bool)
    gate = np.zeros(prep["n_super"], np.int32)
    held = pallas_trace.slot_sources(prep, -1) >= 0
    of_tile = (prep["bmeta1"] >> 1) == tile
    if case == "a_block_on_one_cell":
        assert held[of_tile].all() and of_tile.sum() == 1  # one full block
    if case == "second_block_accumulates":
        assert held[of_tile].all() and of_tile.sum() == 2
    if case == "every_cell_its_own_count":
        assert (held[of_tile].sum(axis=1) > 0).sum() > 100 // sub
    expected = _reference_contribs(prep, psrc, pdst, table, dirty, gate)
    rows = slice(tile * s_rows, (tile + 1) * s_rows)
    assert np.array_equal(expected[rows], want.astype(np.float32))
    assert want.max() <= of_tile.sum() * slots  # exact in f32 by far
    out, steps, contracted = _launch(
        prep, table, dirty, gate, interpret=interpret
    )
    assert np.array_equal(out, expected)
    assert contracted == int(_gathering_blocks(prep, table, dirty, gate).sum())
    assert contracted == int((held[of_tile].sum(axis=1) > 0).sum()) <= steps


@pytest.mark.parametrize("sub", [1, 2])
@pytest.mark.parametrize("case", WORDS_CASES)
def test_contraction_of_packed_words_is_the_segment_sum(case, sub):
    """The kernel's contraction against the numpy segment-sum, on blocks
    made by hand: every cell of a tile hit by its own number of set slots,
    a whole block (then two) on one cell of odd row and odd lane, and set
    sources beside clear ones on the same cell."""
    _check_words_case(case, sub, interpret=True)


@pytest.mark.tpu
@pytest.mark.parametrize("case", WORDS_CASES)
def test_contraction_of_packed_words_compiled(case):
    """The same through Mosaic at the chip's block (32 slot rows): what
    settles the order of the rows inside a packed word on hardware."""
    _check_words_case(case, pallas_trace.SUB_TPU, interpret=False)


# --------------------------------------------------------------------- #
# The walk: two chunks a trip, a slot vreg's gathers in a row
# --------------------------------------------------------------------- #

WALK_CHUNKS = 6
#: per destination tile, the walk chunks its sources lie in (first, count):
#: spans of 1, 2, 3 and 5 chunks, odd ones beside even ones
WALK_WINDOWS = [(0, 1), (1, 2), (2, 3), (1, 5), (5, 1), (4, 2), (3, 3), (0, 5)]
#: the chunk-iterations the blocks of a case take
WALK_CASES = {
    "all_dirty": {1, 2, 3, 5},
    "forced_beside_listed": {1, 2, 3, 4, 5},
    "odd_tail_at_the_lists_end": {1, 2, 3},
    "no_gate_operand": {1, 2, 3, 5},
}


def _walk_layout(sub, group):
    """A layout of one block a tile whose span is the tile's window, and
    the pairs it holds: under 128 pairs a row class and tile."""
    rng = np.random.default_rng(47)
    chunk_nodes = pallas_trace.ROWS * group * pallas_trace.LANE * pallas_trace.WORD_BITS
    n = WALK_CHUNKS * chunk_nodes
    super_sz = GRID_S_ROWS * pallas_trace.LANE
    psrc, pdst = [], []
    for tile, (first, count) in enumerate(WALK_WINDOWS):
        lo, hi = first * chunk_nodes, (first + count) * chunk_nodes
        # both ends of the window hold a source, so the span is the window
        psrc.append(np.r_[lo, hi - 1, rng.integers(lo, hi, 500)])
        pdst.append(tile * super_sz + rng.integers(0, super_sz, 502))
    psrc, pdst = np.concatenate(psrc), np.concatenate(pdst)
    prep = pallas_trace.prepare_pairs(
        psrc, pdst, n, s_rows=GRID_S_ROWS, pad_blocks_pow2=True, sub=sub,
        group=group,
    )
    return prep, psrc, pdst


def _check_walk_case(case, sub, group, interpret):
    prep, psrc, pdst = _walk_layout(sub, group)
    chunk_rows = pallas_trace.ROWS * group
    assert prep["r_rows"] // chunk_rows == WALK_CHUNKS
    rng = np.random.default_rng(3)
    # set bits in every chunk, so a chunk walked into the wrong slots or
    # read from the wrong half shows; what is new differs from the table
    table = rng.integers(0, 1 << 31, (prep["r_rows"], pallas_trace.LANE)).astype(np.int32)
    new = table & rng.integers(0, 1 << 31, table.shape).astype(np.int32)
    dirty = np.ones(WALK_CHUNKS, bool)
    gate = np.zeros(prep["n_super"], np.int32)
    dst_gate = case != "no_gate_operand"
    if case == "forced_beside_listed":
        dirty[2] = False  # listed blocks walk what is left of their windows
        gate[3] = pallas_trace.GATE_FULL  # an odd plain span, over the full table
        gate[5] = pallas_trace.GATE_SKIP
    if case == "odd_tail_at_the_lists_end":
        # the last tile walks chunks 1, 2, 3 of its window 0..4, the
        # list's last three entries: a trip that read one entry more
        # would find chunk 0, clean but in the window, and its bits
        dirty[[0, 4, 5]] = False
    n_iter = _block_iters(prep, dirty, gate)
    assert set(n_iter[n_iter > 0]) == WALK_CASES[case], n_iter[n_iter > 0]
    assert (n_iter[n_iter > 0] % 2).any() and not (n_iter[n_iter > 0] % 2).all()

    # the walks and their trips (two chunks a trip, a trip of one where a
    # block's count is odd) are held to numpy's inside ``_launch``
    out, steps, contracted = _launch(
        prep, table, dirty, gate, new=new, interpret=interpret, group=group,
        dst_gate=dst_gate,
    )
    expected = _reference_contribs(
        prep, psrc, pdst, table, dirty, gate, new, chunk_rows=chunk_rows
    )
    assert expected.any() and np.array_equal(out, expected)
    assert steps == int((n_iter > 0).sum())
    assert contracted == int(
        _gathering_blocks(prep, table, dirty, gate, new, chunk_rows=chunk_rows).sum()
    )


@pytest.mark.parametrize("sub,group", [(1, 1), (2, 2)])
@pytest.mark.parametrize("case", WALK_CASES)
def test_walk_of_two_chunks_a_trip_gathers_every_chunk_once(case, sub, group):
    """Blocks of 1, 2, 3 and 5 chunk-iterations in one launch against the
    segment-sum over the same pairs, bit for bit: a trip walks two chunks,
    an odd count's first chunk has a trip of its own, a forced block walks
    its plain span over the full table beside blocks that walk the dirty
    list over what is new, to the list's last entry and no further, with
    and without the gate operand."""
    _check_walk_case(case, sub, group, interpret=True)


@pytest.mark.tpu
@pytest.mark.parametrize("case", WALK_CASES)
def test_walk_of_two_chunks_a_trip_compiled(case):
    """The same through Mosaic at the chip's geometry (32 slot rows,
    64-row table groups)."""
    _check_walk_case(
        case, pallas_trace.SUB_TPU, pallas_trace.GROUP_TPU, interpret=False
    )
