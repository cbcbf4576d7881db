"""Retracing + donation regression checks for the fused routing engine
(4 emulated devices; subprocess-isolated like the other multi-device helpers).

The contract under test: N consecutive PulseService quanta and repeated
PulseEngine.execute calls with same-shaped pools compile exactly once (the
compiled-executable cache absorbs everything after the first), the resident
arena is uploaded once, and the donated pool buffer is consumed by the
executable (not silently copied)."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as Spec  # noqa: E402

from repro.core import routing  # noqa: E402
from repro.distributed.sharding import mem_mesh  # noqa: E402
from repro.core.engine import PulseEngine  # noqa: E402
from repro.core.structures import btree, linked_list  # noqa: E402
from repro.serving.admission import TraversalRequest  # noqa: E402
from repro.serving.traversal_service import PulseService, StructureSpec  # noqa: E402

RNG = np.random.default_rng(17)
P = 4


def _list_setup(n=64, B=16):
    keys = np.arange(n, dtype=np.int32)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, head = linked_list.build(keys, values, num_shards=P, policy="interleaved")
    it = linked_list.find_iterator()
    q = keys[RNG.integers(0, n, B)].astype(np.int32)
    ptr0, scr0 = it.init(jnp.asarray(q), head)
    return it, ar, ptr0, scr0


def check_repeated_execute_compiles_once():
    """Same-shaped fused executions after the first must be pure cache hits:
    zero traces, zero executable-cache misses."""
    it, ar, ptr0, scr0 = _list_setup()
    mesh = mem_mesh(P)
    eng = PulseEngine(ar, mesh=mesh)
    routing.reset_executable_caches()
    first = eng.execute(it, ptr0, scr0, max_iters=4096)
    assert routing.CACHE_STATS.traces >= 1  # the one compile
    assert routing.CACHE_STATS.misses == 1
    routing.CACHE_STATS.reset()
    for _ in range(4):
        res = eng.execute(it, ptr0, scr0, max_iters=4096)
        np.testing.assert_array_equal(res.scratch, first.scratch)
    assert routing.CACHE_STATS.traces == 0, routing.CACHE_STATS
    assert routing.CACHE_STATS.misses == 0, routing.CACHE_STATS
    assert routing.CACHE_STATS.hits == 4, routing.CACHE_STATS
    print(f"repeated execute ok: {routing.CACHE_STATS}")


def check_service_quanta_compile_once():
    """N consecutive PulseService quanta with fixed slot shapes: one compile
    per (structure, shape), then zero retraces for the rest of the run."""
    n = 96
    lkeys = np.arange(n, dtype=np.int32)
    lvals = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, head = linked_list.build(lkeys, lvals, num_shards=P, policy="interleaved")
    mesh = mem_mesh(P)
    eng = PulseEngine(ar, mesh=mesh)
    svc = PulseService(
        eng,
        {"list": StructureSpec(linked_list.find_iterator(), (head,))},
        slots_per_structure=8,
        quantum=4,
    )
    # warm: first quantum compiles the (iterator, pool-shape) executable
    svc.run([TraversalRequest(0, "list", int(lkeys[1]))])
    svc.metrics = type(svc.metrics)()  # drop warmup accounting
    routing.CACHE_STATS.reset()
    reqs = [
        TraversalRequest(1 + i, "list", int(lkeys[RNG.integers(0, n)]))
        for i in range(24)
    ]
    m = svc.run(reqs)
    assert m.completed == 24
    assert m.rounds >= 3  # several quanta actually ran
    assert routing.CACHE_STATS.traces == 0, routing.CACHE_STATS
    assert routing.CACHE_STATS.misses == 0, routing.CACHE_STATS
    assert routing.CACHE_STATS.hits >= m.engine_calls, (
        routing.CACHE_STATS,
        m.engine_calls,
    )
    print(
        f"service quanta ok: rounds={m.rounds} engine_calls={m.engine_calls} "
        f"{routing.CACHE_STATS}"
    )


def check_resident_arena_uploaded_once():
    """Consecutive executions reuse the device-resident arena buffers."""
    it, ar, ptr0, scr0 = _list_setup()
    mesh = mem_mesh(P)
    routing.reset_executable_caches()
    routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=4096, compact=True, fused=True
    )
    resident = routing._RESIDENT[(id(ar), mesh, "mem")]
    routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=4096, compact=True, fused=True
    )
    assert routing._RESIDENT[(id(ar), mesh, "mem")] is resident
    assert all(not buf.is_deleted() for buf in resident)  # never donated
    print("resident arena ok: one upload, buffers alive")


def check_donated_pool_consumed():
    """The fused executable must consume (donate) the pool buffer it is
    handed -- and must not touch it afterwards (whitebox: call the cached
    executable directly with a pool we control)."""
    it, ar, ptr0, scr0 = _list_setup(B=16)
    mesh = mem_mesh(P)
    routing.reset_executable_caches()
    routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=4096, compact=True, fused=True
    )
    assert len(routing._FUSED_CACHE) == 1
    runner = next(iter(routing._FUSED_CACHE.values()))
    data, bounds, perms = routing._resident_arena(ar, mesh, "mem")
    L = 16  # Bp per shard, as built by distributed_execute for B=16
    pool = jax.device_put(
        routing.empty_records(P * L, it.scratch_words),
        NamedSharding(mesh, Spec("mem")),
    )
    out = runner(pool, data, bounds, perms, jnp.int32(4096), jnp.int32(1 << 16))
    jax.block_until_ready(out[0])
    assert pool.is_deleted(), "pool buffer was not donated to the executable"
    assert not data.is_deleted(), "resident arena must not be donated"
    print("donation ok: pool consumed, arena resident")


def check_pipelined_compiles_once_and_donates():
    """The wavefront-pipelined executable obeys the same cache + donation
    contract as the fused one: repeated same-shaped runs are pure cache hits
    (zero retraces), the handed-in pool buffer is consumed, and the resident
    arena survives.  Fused and pipelined runners coexist in the cache under
    distinct schedule keys."""
    it, ar, ptr0, scr0 = _list_setup()
    mesh = mem_mesh(P)
    routing.reset_executable_caches()
    first, _ = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=4096, compact=True,
        schedule="pipelined",
    )
    assert routing.CACHE_STATS.misses == 1
    routing.CACHE_STATS.reset()
    for _ in range(3):
        rec, _ = routing.distributed_execute(
            it, ar, ptr0, scr0, mesh=mesh, max_iters=4096, compact=True,
            schedule="pipelined",
        )
        np.testing.assert_array_equal(rec, first)
    assert routing.CACHE_STATS.traces == 0, routing.CACHE_STATS
    assert routing.CACHE_STATS.misses == 0, routing.CACHE_STATS
    assert routing.CACHE_STATS.hits == 3, routing.CACHE_STATS
    # a fused run afterwards compiles its own executable (distinct key),
    # leaving the pipelined one cached
    routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=4096, compact=True,
        schedule="fused",
    )
    assert len(routing._FUSED_CACHE) == 2, list(routing._FUSED_CACHE)

    # donation: call the cached pipelined runner directly with our own pool
    key = next(k for k in routing._FUSED_CACHE if "pipelined" in k)
    runner = routing._FUSED_CACHE[key]
    data, bounds, perms = routing._resident_arena(ar, mesh, "mem")
    L = 16
    pool = jax.device_put(
        routing.empty_records(P * L, it.scratch_words),
        NamedSharding(mesh, Spec("mem")),
    )
    out = runner(pool, data, bounds, perms, jnp.int32(4096), jnp.int32(1 << 16))
    jax.block_until_ready(out[0])
    assert pool.is_deleted(), "pipelined runner must donate the pool buffer"
    assert not data.is_deleted(), "resident arena must not be donated"
    print("pipelined cache+donation ok")


def check_pipelined_service_quanta_compile_once():
    """PulseService quanta on the pipelined schedule (the auto default for
    a meshed engine): one compile, then zero retraces across rounds."""
    n = 96
    lkeys = np.arange(n, dtype=np.int32)
    lvals = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, head = linked_list.build(lkeys, lvals, num_shards=P, policy="interleaved")
    mesh = mem_mesh(P)
    eng = PulseEngine(ar, mesh=mesh)
    svc = PulseService(
        eng,
        {"list": StructureSpec(linked_list.find_iterator(), (head,))},
        slots_per_structure=8,
        quantum=4,
        schedule="pipelined",
    )
    svc.run([TraversalRequest(0, "list", int(lkeys[1]))])
    svc.metrics = type(svc.metrics)()
    routing.CACHE_STATS.reset()
    reqs = [
        TraversalRequest(1 + i, "list", int(lkeys[RNG.integers(0, n)]))
        for i in range(24)
    ]
    m = svc.run(reqs)
    assert m.completed == 24
    assert routing.CACHE_STATS.traces == 0, routing.CACHE_STATS
    assert routing.CACHE_STATS.misses == 0, routing.CACHE_STATS
    print(
        f"pipelined service quanta ok: rounds={m.rounds} "
        f"engine_calls={m.engine_calls} {routing.CACHE_STATS}"
    )


if __name__ == "__main__":
    assert jax.device_count() == P, jax.devices()
    check_repeated_execute_compiles_once()
    check_service_quanta_compile_once()
    check_resident_arena_uploaded_once()
    check_donated_pool_consumed()
    check_pipelined_compiles_once_and_donates()
    check_pipelined_service_quanta_compile_once()
    print("ALL FUSED CHECKS PASSED")
