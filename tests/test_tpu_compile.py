"""Compile the main path for a described TPU v5e, without the chip.

The TPU compiler is installed with jaxlib and compiles for a topology that is
described, not attached: these tests catch what the chip's compilers (XLA
and Mosaic) would refuse -- unaligned kernel slices, loads from HBM refs,
unsupported primitives -- at no chip time.  Nothing runs, so results are
checked elsewhere (``test_kernels.py`` in interpret mode, ``chip_smoke.py``
on the chip).

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and the test runner's workers
all import this file.  The persistent compilation cache is off around the
compiles, because a compile for a described chip cannot be read back here.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import pulse_paper
from repro.core.structures import btree, hash_table

B = 256  # lanes per kernel call / slots per compiled engine batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _arena_rows() -> int:
    ws, wt = pulse_paper.WEBSERVICE, pulse_paper.WIREDTIGER
    return ws.n_keys + btree.node_estimate(wt.n_keys)


@pytest.mark.parametrize(
    "name,width",
    [("hash", hash_table.NODE_WORDS), ("btree", btree.NODE_WORDS)],
)
def test_pulse_chase_compiles_for_v5e(one_chip, no_compile_cache, name, width):
    """The kernel at the hash (4-word) and B+tree (20-word) record widths,
    over the paper-sized pooled arena: Mosaic accepts it, and the compiled
    program holds the custom call (the kernel was not lowered away)."""
    from repro.kernels.pulse_chase import ops

    it = (
        hash_table.find_iterator(pulse_paper.WEBSERVICE.n_buckets)
        if name == "hash"
        else btree.find_iterator()
    )
    logic = ops.iterator_logic(it)
    S = it.scratch_words

    def chase(arena, ptr, scr, st, iters):
        return ops._pulse_chase_donated(
            ops.lane_pad(arena), ptr, scr, st, iters, logic_fn=logic,
            num_steps=8, wave=8,
        )

    compiled = (
        jax.jit(chase)
        .lower(
            _sds((_arena_rows(), width), one_chip),
            _sds((B,), one_chip),
            _sds((B, S), one_chip),
            _sds((B,), one_chip),
            _sds((B,), one_chip),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_xla_step_compiles_for_v5e(one_chip, no_compile_cache):
    """The engine's single-node read step (``execute_batched`` under the
    same jit the engine builds) over the WebService arena's shapes."""
    from repro.core.arena import HEAP_WORDS, Arena
    from repro.core.iterator import execute_batched

    ws = pulse_paper.WEBSERVICE
    it = hash_table.find_iterator(ws.n_buckets)
    arena = Arena(
        data=_sds((ws.n_keys, hash_table.NODE_WORDS), one_chip),
        bounds=_sds((2,), one_chip),
        perms=_sds((1,), one_chip),
        heap=_sds((1, HEAP_WORDS), one_chip),
    )
    step = jax.jit(
        lambda ar, p, s, budget: execute_batched(it, ar, p, s, max_iters=budget),
        donate_argnums=(1, 2),
    )
    compiled = step.lower(
        arena,
        _sds((B,), one_chip),
        _sds((B, it.scratch_words), one_chip),
        _sds((), one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem is None or mem.argument_size_in_bytes >= ws.n_keys * 4 * 4


@pytest.mark.parametrize("mutate", [False, True], ids=["read", "write"])
def test_sharded_pipelined_loop_compiles_for_v5e_2x2(topo, no_compile_cache, mutate):
    """The 4-shard wavefront-pipelined loop (the schedule ``auto`` picks) on
    a mesh of the four described chips, for B+tree finds and for B+tree
    updates (the write commit superstep): the shard_map program with its
    all_to_all fabric compiles, and the collective is in it."""
    from repro.core import routing
    from repro.distributed.sharding import mem_mesh

    mesh = mem_mesh(4, devices=topo.devices)
    it = btree.update_iterator() if mutate else btree.find_iterator()
    shards, L = 4, 32
    rows = -(-_arena_rows() // shards) * shards
    runner = routing.get_fused_runner(
        it, mesh, "mem",
        num_shards=shards, pool_rows=shards * L, scratch_words=it.scratch_words,
        k_local=4, max_supersteps=1 << 16, base_capacity=L // shards,
        min_link_capacity=8, return_to_cpu=False, compact=True,
        schedule="pipelined", mutate=mutate,
    )
    rep = NamedSharding(mesh, P())
    by_shard = NamedSharding(mesh, P("mem", None))
    MW = routing.mut_width(btree.NODE_WORDS) if mutate else 0
    R = routing.record_width(it.scratch_words, MW)
    pool = _sds((shards * L, R), by_shard)
    data = _sds((rows, btree.NODE_WORDS), by_shard)
    tables = (_sds((shards + 1,), rep), _sds((shards,), rep))
    scalars = (_sds((), rep), _sds((), rep))
    if mutate:
        from repro.core.arena import HEAP_WORDS

        heap = _sds((shards, HEAP_WORDS), by_shard)
        args = (pool, data, heap, *tables, *scalars)
    else:
        args = (pool, data, *tables, *scalars)
    compiled = runner.lower(*args).compile()
    assert "all-to-all" in compiled.as_text()
