"""Compile the main-path kernels for a described TPU v5e — no chip needed.

The TPU compiler is installed with libtpu and compiles for a topology
that is described, not attached: ``jit(...).lower(shapes).compile()``
raises here what the chip's compiler would raise there (a Mosaic
lowering failure, a slice off the tiling, too much VMEM/SMEM, a program
that does not fit HBM).  Nothing runs, so this says nothing about
verdicts or times — ``chip_smoke.py`` on the chip does.

Everything about the topology happens inside the module-scoped ``topo``
fixture (never at import, in a ``skipif`` or a ``parametrize`` argument):
only one process may load libtpu, and every xdist worker imports every
test file.  The kernels need only their geometry, so every operand is a
``ShapeDtypeStruct`` and no graph is packed.  Keep these tests in this
one file — a second file could land on another worker, whose fixture
would then skip.
"""

import numpy as np
import pytest

from uigc_tpu.ops import pallas_decremental as pd
from uigc_tpu.ops import pallas_trace as pt

LANE = pt.LANE
BLOCK_ROWS = pt.ROWS * pt.SUB_TPU
GROUP_ROWS = pt.ROWS * pt.GROUP_TPU

#: BASELINE config 5 as ``IncrementalPallasLayout.rebuild`` packs it
#: (powerlaw_actor_graph(10M, seed 0): 49.7M pairs, quantum-padded
#: blocks) — the geometry chip_smoke.py prints on the chip.
GEOM_10M = dict(n=10_000_000, n_blocks=24_576, n_super=2442, r_rows=2496)
#: chain_actor_graph(1M) (the benchmark's ``chain-1m``: 2.0M pairs, 4
#: walk chunks), as ``IncrementalPallasLayout.rebuild`` packs it
GEOM_CHAIN_1M = dict(n=1_000_000, n_blocks=4096, n_super=245, r_rows=256)
#: the served cell's resident tree (the benchmark's ``tree-100k``: 100,001
#: actors in a backend capacity of 131,072, 300,001 pairs, one walk
#: chunk), as ``IncrementalPallasLayout.rebuild`` packs it
GEOM_TREE_100K = dict(n=131_072, n_blocks=1024, n_super=32, r_rows=64)
#: the engine cell's graph (the benchmark's ``engine-fold-10m``: the 10M
#: graph folded into 2^24 slots): the widest table pair the kernel holds
#: in VMEM, 2 x 2 MB
GEOM_ENGINE_16M = dict(n=1 << 24, n_blocks=24_576, n_super=4096, r_rows=4096)
#: kron_actor_graph(scale 22) (the benchmark's ``kron-s22``: 69.4M pairs,
#: 16 walk chunks), as ``IncrementalPallasLayout.rebuild`` packs it
GEOM_KRON_S22 = dict(n=1 << 22, n_blocks=24_576, n_super=1024, r_rows=1024)
#: a 20k-actor layout (pow2-padded blocks)
GEOM_SMALL = dict(n=20_000, n_blocks=128, n_super=5, r_rows=64)
#: 10M over four shards, as ``pack_shard_layouts`` packs it
MESH_10M = dict(n_pad=10_010_624, n_blocks=16_384, r_rows=2496, bucket_m=1024)
MESH_SMALL = dict(n_pad=65_536, n_blocks=256, r_rows=64, bucket_m=1024)
#: the benchmark's ``mesh4-10m``: the 10M graph folded into 2^24 slots over
#: four shards (destination supertiles dealt round-robin: 4,096 blocks a
#: shard), the insert buckets at the size the window runs (grown in the
#: warm-up from the floor of 1,024 to twice what a wake's 10,000 new
#: references put into the fullest shard, ~2,550: ``mesh.py _grow_buckets``)
MESH_ENGINE_16M = dict(n_pad=1 << 24, n_blocks=4096, r_rows=4096, bucket_m=8192)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but never read back without one; keep it off around these.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layout_structs(geom, s):
    rows = geom["n_blocks"] * BLOCK_ROWS
    return [
        _struct((geom["n_blocks"],), np.int32, s),  # bmeta1
        _struct((geom["n_blocks"],), np.int32, s),  # bmeta2
        _struct((rows, LANE), np.int32, s),  # row_pos
        _struct((rows, LANE), np.int32, s),  # emeta
    ]


def _node_structs(geom, s):
    # recv_count is int64 on the host; x64 is off, so the device sees int32
    return [
        _struct((geom["n"],), np.uint8, s),
        _struct((geom["n"],), np.int32, s),
    ]


def _spec(geom):
    return (("dense", geom["n_blocks"], pt.SUB_TPU, pt.GROUP_TPU),)


def _lower_wake(fn, geom, s, mode):
    words = _struct((geom["r_rows"], LANE), np.int32, s)
    # suspects (2), the previous fixpoint (5 word tables and the walks
    # of its last derivation from nothing)
    args = _node_structs(geom, s) + [words] * 7 + [_struct((), np.int32, s)]
    if mode in (pt.MODE_JUMP, pt.MODE_AUTO):
        args.append(_struct((geom["n"] + 1,), np.int32, s))
    return fn.lower(*args, *_layout_structs(geom, s))


def _compile_wake(geom, s, mode):
    fn = pd.get_wake_fn(
        geom["n"], _spec(geom), geom["n_super"], geom["r_rows"], pt.S_ROWS,
        interpret=False, mode=mode,
    )
    return _lower_wake(fn, geom, s, mode).compile()


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


#: what the chip's scalar memory holds (``pt._pad_blocks_target``)
SMEM_BUDGET = 1 << 20


def _scalar_operand_bytes(geom) -> int:
    """The propagate kernel's scalar-prefetch operands at this geometry:
    the dirty prefix and list, the gate, ``bmeta1``, ``bmeta2`` and the
    list of active blocks."""
    n_chunks = geom["r_rows"] // GROUP_ROWS
    return 4 * (2 * n_chunks + 1 + geom["n_super"] + 3 * geom["n_blocks"])


@pytest.mark.parametrize(
    "geom",
    [GEOM_10M, GEOM_CHAIN_1M, GEOM_TREE_100K, GEOM_SMALL, GEOM_ENGINE_16M, GEOM_KRON_S22],
    ids=["10m", "chain-1m", "tree-100k", "small", "engine-16m", "kron-s22"],
)
def test_propagate_launch_compiles_with_its_list(one_chip, geom):
    """One launch as every caller makes it (``build_propagate``'s callable:
    the list of active blocks in XLA, then a grid as long as the list):
    one Mosaic kernel (with the test of what a block gathered around its
    contraction and the SMEM counter of the steps that contracted), its
    scalar operands under the SMEM budget, and beside the contributions
    the count of steps it took, of those that contracted, of the
    chunk-iterations their walks take and of the loop trips they take
    them in."""
    import jax

    propagate = pt.build_propagate(
        geom["n_blocks"], geom["n_super"], geom["r_rows"], pt.S_ROWS, False,
        sub=pt.SUB_TPU, group=pt.GROUP_TPU, dst_gate=True,
    )
    n_chunks = geom["r_rows"] // GROUP_ROWS
    bmeta1, bmeta2, row_pos, emeta = _layout_structs(geom, one_chip)
    compiled = jax.jit(propagate.with_steps).lower(
        _struct((n_chunks + 1,), np.int32, one_chip),  # d
        _struct((n_chunks,), np.int32, one_chip),  # l
        _struct((geom["n_super"],), np.int32, one_chip),  # gate
        bmeta1, bmeta2,
        # the table over its new bits (``pt.walk_tables``)
        _struct((2 * geom["r_rows"], LANE), np.int32, one_chip),
        row_pos, emeta,
    ).compile()
    assert _mosaic_calls(compiled) == 1
    assert _scalar_operand_bytes(geom) < SMEM_BUDGET
    out, steps, contracted, walks, trips = compiled.out_info
    assert out.shape == (geom["n_super"] * pt.S_ROWS, LANE)
    assert steps.shape == () and steps.dtype == np.int32
    assert contracted.shape == () and contracted.dtype == np.int32
    assert walks.shape == () and walks.dtype == np.int32
    assert trips.shape == () and trips.dtype == np.int32


def test_decremental_wake_compiles_at_10m(one_chip):
    compiled = _compile_wake(GEOM_10M, one_chip, pt.MODE_AUTO)
    assert _mosaic_calls(compiled) >= 2  # closure + repair fixpoints
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30
    # the next wake's previous state: five word tables and the carried
    # walks of the last derivation from nothing, then the counters
    *words, walks, stats = compiled.out_info
    assert [w.shape for w in words] == [(GEOM_10M["r_rows"], LANE)] * 5
    assert walks.shape == () and walks.dtype == np.int32
    assert stats["closure_bailed"].shape == stats["closure_spent"].shape == ()
    assert stats["kernel_steps"].shape == stats["kernel_steps_full"].shape == ()
    assert stats["kernel_contractions"].shape == stats["kernel_chunk_walks"].shape == ()
    assert stats["kernel_walk_trips"].shape == ()
    # three n_blocks-long int32 operands in SMEM (bmeta1, bmeta2 and the
    # list of active blocks) beside the gate and the dirty lists
    assert _scalar_operand_bytes(GEOM_10M) == 304_996 < SMEM_BUDGET


@pytest.mark.parametrize("mode", [pt.MODE_AUTO, pt.MODE_JUMP])
def test_decremental_wake_compiles_at_chain_1m(one_chip, mode):
    """The geometry on which the jump is taken: its sub-scopes are in the
    compiled text, and ``auto`` prices a jump sweep at 10 chunk walks."""
    compiled = _compile_wake(GEOM_CHAIN_1M, one_chip, mode)
    assert _mosaic_calls(compiled) >= 2
    text = compiled.as_text()
    for part in ("hits", "double", "pack"):
        assert f"/jump/{part}/" in text, part
    fn = pd.get_wake_fn(
        GEOM_CHAIN_1M["n"], _spec(GEOM_CHAIN_1M), GEOM_CHAIN_1M["n_super"],
        GEOM_CHAIN_1M["r_rows"], pt.S_ROWS, interpret=False, mode=mode,
    )
    assert fn.jump_price == 10


def test_decremental_wake_compiles_at_tree_100k(one_chip):
    """The served cell's geometry: one walk chunk, so every sweep counts
    as sparse and ``auto`` prices a jump sweep at a single chunk walk."""
    compiled = _compile_wake(GEOM_TREE_100K, one_chip, pt.MODE_AUTO)
    assert _mosaic_calls(compiled) >= 2
    assert "/jump/double/" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1 << 30
    fn = pd.get_wake_fn(
        GEOM_TREE_100K["n"], _spec(GEOM_TREE_100K), GEOM_TREE_100K["n_super"],
        GEOM_TREE_100K["r_rows"], pt.S_ROWS, interpret=False, mode=pt.MODE_AUTO,
    )
    assert fn.jump_price == 1


def test_decremental_wake_compiles_at_kron_s22(one_chip):
    """The Kronecker cell's geometry: 16 walk chunks of a graph that
    dirties them all, where ``auto`` prices a jump sweep at 29 chunk
    walks (the chip's run never pays it: its sweeps are dense)."""
    compiled = _compile_wake(GEOM_KRON_S22, one_chip, pt.MODE_AUTO)
    assert _mosaic_calls(compiled) >= 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4 << 30
    fn = pd.get_wake_fn(
        GEOM_KRON_S22["n"], _spec(GEOM_KRON_S22), GEOM_KRON_S22["n_super"],
        GEOM_KRON_S22["r_rows"], pt.S_ROWS, interpret=False, mode=pt.MODE_AUTO,
    )
    assert fn.jump_price == 29


def test_verdict_reduce_compiles_at_the_engine_cells_capacity(one_chip):
    """The sweep's reduce of the wake's words (``verdict_words``) at
    2^24 slots: one fusion, 2 MB of words and a scalar out."""
    import jax.numpy as jnp

    words = _struct((4096, LANE), jnp.int32, one_chip)
    compiled = pd.verdict_reduce().lower(words, words).compile()
    assert compiled.memory_analysis().output_size_in_bytes < 3 << 20
    assert "popcnt" in compiled.as_text()


@pytest.mark.parametrize("slots", [4096, 1 << 17, 1 << 21], ids=["served", "engine", "share"])
def test_node_patch_compiles_at_the_engine_cells_capacity(one_chip, slots):
    """The scatter that brings the device's ``flags`` and ``recv_count``
    up to the host's (``arrays._patch_fn``) at 2^24 slots: a served
    round's padded length, an engine wake's, and the longest before a
    wake uploads whole.  Both arrays are donated: the outputs alias
    them, and the program holds no third copy."""
    import jax.numpy as jnp

    from uigc_tpu.engines.crgc import arrays

    n = GEOM_ENGINE_16M["n"]
    assert slots <= arrays._patch_pad(n // arrays._PATCH_SHARE)
    compiled = arrays._patch_fn().lower(
        _struct((n,), jnp.uint8, one_chip), _struct((n,), jnp.int32, one_chip),
        _struct((slots,), jnp.int32, one_chip), _struct((slots,), jnp.uint8, one_chip),
        _struct((slots,), jnp.int32, one_chip),
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 5 * n  # a byte and an int32 a slot
    assert memory.output_size_in_bytes - 5 * n < 4096  # and the tuple that holds them
    assert memory.temp_size_in_bytes < 5 * n


@pytest.mark.parametrize(
    "mode", pt.TRACE_MODES, ids=lambda m: f"wake-{m}-plain"
)
def test_trace_modes_compile(one_chip, mode):
    assert _mosaic_calls(_compile_wake(GEOM_SMALL, one_chip, mode)) >= 1


@pytest.mark.parametrize("mode", pt.TRACE_MODES)
def test_wake_program_counts_and_names(one_chip, mode):
    """The one wake program per mode: its outputs hold the sweep
    counters, and the compiled text names its phases and its kernel, so a
    device trace of it can be summed by phase."""
    compiled = _compile_wake(GEOM_SMALL, one_chip, mode)
    *words, walks, stats = compiled.out_info
    assert len(words) == 5 and walks.shape == ()
    assert stats["closure_sweeps"].shape == stats["n_sweeps"].shape == ()
    assert stats["closure_bailed"].shape == stats["closure_spent"].shape == ()
    assert stats["gated_tiles"].shape == ()
    assert stats["kernel_steps"].shape == stats["kernel_steps_full"].shape == ()
    assert stats["kernel_contractions"].shape == ()
    for key in ("kernel_chunk_walks", "kernel_walk_trips"):
        assert stats[key].shape == () and stats[key].dtype == np.int32
    assert stats["jump_sweeps"].shape == stats["jump_spent"].shape == ()
    for key in ("dirty_chunks", "tiles_skipped", "pull_on", "jump_on"):
        assert stats[key].shape == (pt.MAX_SWEEP_STATS,)
    text = compiled.as_text()
    for phase in ("closure", "repair"):
        assert f"{pd.WAKE_SCOPE}/{phase}" in text
    assert pt.KERNEL_NAME in text


def _kernel_bodies(text) -> set:
    """The distinct Mosaic kernels of a lowered program, by their
    serialized bodies."""
    import re

    return set(re.findall(r'tpu_custom_call.*?backend_config = "([^"]*)"', text))


class _EnvSpy(dict):
    """``os.environ`` as a dict that notes the keys it is asked for."""

    def __init__(self, env):
        super().__init__(env)
        self.asked = set()

    def get(self, key, default=None):
        self.asked.add(key)
        return super().get(key, default)

    def __getitem__(self, key):
        self.asked.add(key)
        return super().__getitem__(key)

    def __contains__(self, key):
        self.asked.add(key)
        return super().__contains__(key)


def test_one_propagate_kernel_and_no_knob_in_the_environment(
    one_chip, monkeypatch
):
    """The wake program at 10M launches ONE kernel body from its two
    fixpoints (there is no second build of the contraction's operands),
    and building and lowering it asks the environment for no variable of
    this repo's: what is lowered depends on the geometry alone."""
    import os

    geom = GEOM_10M
    spy = _EnvSpy(os.environ)
    monkeypatch.setattr(os, "environ", spy)
    # built here and not taken from ``get_wake_fn``'s cache, which an
    # earlier test has filled: the spy has to see the build
    fn = pd._build_wake_fn(
        geom["n"], _spec(geom), geom["n_super"], geom["r_rows"], pt.S_ROWS,
        False, mode=pt.MODE_AUTO,
    )
    text = _lower_wake(fn, geom, one_chip, pt.MODE_AUTO).as_text()
    assert text.count("tpu_custom_call") == 2  # closure and repair
    assert len(_kernel_bodies(text)) == 1
    assert not [k for k in spy.asked if k.upper().startswith("UIGC")]


@pytest.mark.parametrize(
    "geom", [MESH_SMALL, MESH_10M, MESH_ENGINE_16M], ids=["64k", "10m", "16m-grown"]
)
@pytest.mark.parametrize("program", ["trace", "wake"])
def test_sharded_programs_compile(topo, program, geom):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from uigc_tpu.parallel import sharded_trace as st

    D = 4
    mesh = Mesh(np.array(topo.devices[:D]), ("gc",))
    nodes = NamedSharding(mesh, P("gc"))
    dev = NamedSharding(mesh, P("gc", None))
    dev3 = NamedSharding(mesh, P("gc", None, None))
    n_pad, nb = geom["n_pad"], geom["n_blocks"]
    make = (
        st.make_sharded_pallas_trace
        if program == "trace"
        else st.make_sharded_decremental_wake
    )
    fn = make(
        mesh, n_pad, n_pad // D, nb, geom["r_rows"], pt.S_ROWS,
        geom["bucket_m"], interpret=False, sub=pt.SUB_TPU,
        group=pt.GROUP_TPU, mode=pt.MODE_AUTO,
    )
    args = [
        _struct((n_pad,), np.uint8, nodes),
        _struct((n_pad,), np.int32, nodes),
    ]
    if program == "wake":
        args += [_struct((n_pad // 32,), np.int32, nodes)] * 7
        args += [_struct((), np.int32, NamedSharding(mesh, P()))]  # walks
    args += [
        _struct((D, nb), np.int32, dev),
        _struct((D, nb), np.int32, dev),
        _struct((D, nb * BLOCK_ROWS, LANE), np.int32, dev3),
        _struct((D, nb * BLOCK_ROWS, LANE), np.int32, dev3),
        _struct((D, geom["bucket_m"]), np.int32, dev),
        _struct((D, geom["bucket_m"]), np.int32, dev),
        _struct((n_pad + 1,), np.int32, NamedSharding(mesh, P())),
    ]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (1 if program == "trace" else 2)
    assert "all-gather" in text


@pytest.mark.parametrize("geom", [MESH_SMALL, MESH_10M, MESH_ENGINE_16M], ids=["64k", "10m", "16m"])
def test_the_sharded_verdict_compiles_and_leaves_in_slot_order_a_quarter_a_chip(topo, geom):
    """The mesh's readback reduce (``make_sharded_verdict``): the garbage
    words interleaved from owner-major back into slot order across the
    chips, which is data every chip sends every other."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from uigc_tpu.parallel import sharded_trace as st

    D = 4
    mesh = Mesh(np.array(topo.devices[:D]), ("gc",))
    words = _struct((geom["n_pad"] // 32,), np.int32, NamedSharding(mesh, P("gc")))
    compiled = st.make_sharded_verdict(mesh).lower(words, words).compile()
    garbage_w, marked = compiled.output_shardings
    assert garbage_w.is_equivalent_to(NamedSharding(mesh, P("gc")), 1)
    assert marked.is_fully_replicated
    # an all-to-all where a shard's rows divide by the chips, else an
    # all-gather and a slice
    assert any(op in compiled.as_text() for op in ("all-to-all", "all-gather"))
