"""Compacted-superstep equivalence checks (4 emulated devices, small sizes --
the fast-lane companion to helpers/distributed_checks.py)."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import routing  # noqa: E402
from repro.distributed.sharding import mem_mesh  # noqa: E402
from repro.core.iterator import execute_batched  # noqa: E402
from repro.core.structures import (  # noqa: E402
    bst,
    btree,
    hash_table,
    linked_list,
    skiplist,
)

RNG = np.random.default_rng(5)
P = 4


def check_compact_equals_uncompacted():
    """Compaction must be schedule-only: identical ptr/scratch/status/iters,
    strictly less total wire, on a skewed (half-shallow/half-deep) workload."""
    n, B = 192, 64
    keys = np.arange(n, dtype=np.int32)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, head = linked_list.build(keys, values, num_shards=P, policy="interleaved")
    it = linked_list.find_iterator()
    q = np.concatenate(
        [RNG.integers(0, 8, B // 2), RNG.integers(n - 32, n, B // 2)]
    ).astype(np.int32)
    ptr0, scr0 = it.init(jnp.asarray(q), head)
    mesh = mem_mesh(P)

    o_ptr, o_scr, o_status, o_iters = execute_batched(it, ar, ptr0, scr0, max_iters=4096)

    rec_u, st_u = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=4096, k_local=4, compact=False
    )
    rec_c, st_c = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=4096, k_local=4, compact=True
    )
    for rec in (rec_u, rec_c):
        np.testing.assert_array_equal(rec[:, routing.F_SCRATCH:], np.asarray(o_scr))
        np.testing.assert_array_equal(rec[:, routing.F_STATUS], np.asarray(o_status))
        np.testing.assert_array_equal(rec[:, routing.F_ITERS], np.asarray(o_iters))
    assert st_c.total_wire_words < st_u.total_wire_words, (
        st_c.total_wire_words,
        st_u.total_wire_words,
    )
    # once half the batch has finished, the compacted payload must shrink:
    # every routed superstep past that point ships at a reduced capacity
    half_idx = next(i for i, a in enumerate(st_c.active_per_step) if a <= B // 2)
    base = st_u.wire_words_per_step[0]
    tail = [w for w in st_c.wire_words_per_step[half_idx:]]
    assert float(np.mean(tail)) <= 0.7 * base, (np.mean(tail), base)
    print(
        f"compact ok: wire {st_c.total_wire_words} < {st_u.total_wire_words}, "
        f"local_only={st_c.local_only_steps}/{st_c.supersteps}"
    )


def check_compact_handles_faults():
    """FAULTed traversals must retire in place without being lost."""
    n, B = 64, 16
    keys = np.arange(n, dtype=np.int32)
    values = RNG.integers(0, 100, n).astype(np.int32)
    ar, head = linked_list.build(keys, values, num_shards=P)
    it = linked_list.find_iterator()
    q = keys[RNG.integers(0, n, B)].astype(np.int32)
    ptr0, scr0 = it.init(jnp.asarray(q), head)
    # corrupt half the start pointers -> switch-level fault
    ptr0 = jnp.asarray(np.where(np.arange(B) % 2 == 0, 10**6, np.asarray(ptr0)))
    mesh = mem_mesh(P)
    rec, st = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=256, compact=True
    )
    assert rec.shape[0] == B, "conservation under compaction"
    from repro.core.iterator import STATUS_DONE, STATUS_FAULT

    assert (rec[::2, routing.F_STATUS] == STATUS_FAULT).all()
    assert (rec[1::2, routing.F_STATUS] == STATUS_DONE).all()
    print("compact fault ok")


def _five_structures(n=96, B=32):
    """(name, iterator, arena, ptr0, scratch0, max_iters) for every structure
    family, interleaved across shards, with a hit/miss query mix."""
    vals = RNG.integers(0, 10**6, n).astype(np.int32)
    cases = []

    keys = np.arange(n, dtype=np.int32)
    ar, head = linked_list.build(keys, vals, num_shards=P, policy="interleaved")
    it = linked_list.find_iterator()
    q = np.concatenate([keys[RNG.integers(0, n, B - 4)], np.full(4, 10**6)])
    p0, s0 = it.init(jnp.asarray(q.astype(np.int32)), head)
    cases.append(("list", it, ar, p0, s0, 4096))

    keys = np.sort(RNG.choice(np.arange(10**6), n, replace=False).astype(np.int32))
    ar, root, _ = bst.build(keys, vals, num_shards=P, policy="interleaved")
    it = bst.find_iterator()
    q = np.concatenate([keys[: B // 2], RNG.integers(10**6, 2 * 10**6, B // 2)])
    p0, s0 = it.init(jnp.asarray(q.astype(np.int32)), root)
    cases.append(("bst", it, ar, p0, s0, 256))

    ar, root, _ = btree.build(keys, vals, num_shards=P, policy="interleaved")
    it = btree.find_iterator()
    p0, s0 = it.init(jnp.asarray(q.astype(np.int32)), root)
    cases.append(("btree", it, ar, p0, s0, 64))

    ar, heads = hash_table.build(keys, vals, 16, num_shards=P, policy="interleaved")
    it = hash_table.find_iterator(16)
    p0, s0 = it.init(jnp.asarray(q.astype(np.int32)), jnp.asarray(heads))
    cases.append(("hash", it, ar, p0, s0, 1024))

    ar, shead = skiplist.build(keys, vals, num_shards=P, policy="interleaved")
    it = skiplist.find_iterator()
    p0, s0 = it.init(jnp.asarray(q.astype(np.int32)), shead)
    cases.append(("skip", it, ar, p0, s0, 1024))
    return cases


def check_fused_equivalence_all_structures():
    """The fused device-resident loop must be bit-identical to the PR 1
    host-dispatched compacted schedule AND to the BSP oracle, for all five
    structure families -- including crossings and the schedule itself
    (supersteps / wire words / local-only counts), since the fused loop
    re-derives the exact same ladder decisions on-device."""
    mesh = mem_mesh(P)
    for name, it, ar, p0, s0, max_iters in _five_structures():
        o_ptr, o_scr, o_status, o_iters = execute_batched(
            it, ar, p0, s0, max_iters=max_iters
        )
        rec_d, st_d = routing.distributed_execute(
            it, ar, p0, s0, mesh=mesh, max_iters=max_iters, compact=True, fused=False
        )
        rec_f, st_f = routing.distributed_execute(
            it, ar, p0, s0, mesh=mesh, max_iters=max_iters, compact=True, fused=True
        )
        # full wire records (id/home/ptr/status/iters/hops/scratch) identical
        np.testing.assert_array_equal(rec_f, rec_d, err_msg=name)
        np.testing.assert_array_equal(
            rec_f[:, routing.F_SCRATCH:], np.asarray(o_scr), err_msg=name
        )
        np.testing.assert_array_equal(
            rec_f[:, routing.F_STATUS], np.asarray(o_status), err_msg=name
        )
        np.testing.assert_array_equal(
            rec_f[:, routing.F_ITERS], np.asarray(o_iters), err_msg=name
        )
        assert st_f.supersteps == st_d.supersteps, (name, st_f, st_d)
        assert st_f.total_wire_words == st_d.total_wire_words, (name, st_f, st_d)
        assert st_f.local_only_steps == st_d.local_only_steps, (name, st_f, st_d)
        print(
            f"fused {name} ok: steps={st_f.supersteps} "
            f"wire={st_f.total_wire_words} local_only={st_f.local_only_steps}"
        )


def check_pipelined_equivalence_all_structures():
    """The wavefront-pipelined schedule (and its ppermute-ring fabric) must
    be bit-identical to the fused serialized schedule AND the BSP oracle for
    all five structure families: full wire records (id/home/ptr/status/iters/
    hops/scratch), superstep counts, wire words, and local-only counts.  The
    pipelined loop re-derives the exact same ladder decisions from the same
    stale-by-one merged counts; overlap only reorders independent dataflow."""
    mesh = mem_mesh(P)
    for name, it, ar, p0, s0, max_iters in _five_structures():
        o_ptr, o_scr, o_status, o_iters = execute_batched(
            it, ar, p0, s0, max_iters=max_iters
        )
        rec_f, st_f = routing.distributed_execute(
            it, ar, p0, s0, mesh=mesh, max_iters=max_iters, compact=True,
            schedule="fused",
        )
        for fabric in ("dense", "ring"):
            rec_p, st_p = routing.distributed_execute(
                it, ar, p0, s0, mesh=mesh, max_iters=max_iters, compact=True,
                schedule="pipelined", fabric=fabric,
            )
            tag = f"{name}/{fabric}"
            np.testing.assert_array_equal(rec_p, rec_f, err_msg=tag)
            np.testing.assert_array_equal(
                rec_p[:, routing.F_SCRATCH:], np.asarray(o_scr), err_msg=tag
            )
            np.testing.assert_array_equal(
                rec_p[:, routing.F_STATUS], np.asarray(o_status), err_msg=tag
            )
            np.testing.assert_array_equal(
                rec_p[:, routing.F_ITERS], np.asarray(o_iters), err_msg=tag
            )
            assert st_p.supersteps == st_f.supersteps, (tag, st_p, st_f)
            assert st_p.total_wire_words == st_f.total_wire_words, (tag, st_p, st_f)
            assert st_p.local_only_steps == st_f.local_only_steps, (tag, st_p, st_f)
            assert st_p.schedule == "pipelined" and st_p.fabric == fabric
        print(
            f"pipelined {name} ok (dense+ring): steps={st_p.supersteps} "
            f"wire={st_p.total_wire_words} local_only={st_p.local_only_steps}"
        )


def check_pipelined_kernel_local_backend():
    """Threading the local chase through the pulse_chase kernel's vectorized
    iterator body must not change a bit (list exercises the next/end pair,
    btree the step_fn ISA path)."""
    mesh = mem_mesh(P)
    for name, it, ar, p0, s0, max_iters in _five_structures()[:3]:
        rec_x, st_x = routing.distributed_execute(
            it, ar, p0, s0, mesh=mesh, max_iters=max_iters, compact=True,
            schedule="pipelined", local_backend="xla",
        )
        rec_k, st_k = routing.distributed_execute(
            it, ar, p0, s0, mesh=mesh, max_iters=max_iters, compact=True,
            schedule="pipelined", local_backend="kernel",
        )
        np.testing.assert_array_equal(rec_k, rec_x, err_msg=name)
        assert st_k.supersteps == st_x.supersteps, name
    print("pipelined kernel local-backend ok (3 structures)")


def check_pipelined_handles_faults():
    """Switch-level faults retire identically on the pipelined path."""
    n, B = 64, 16
    keys = np.arange(n, dtype=np.int32)
    values = RNG.integers(0, 100, n).astype(np.int32)
    ar, head = linked_list.build(keys, values, num_shards=P)
    it = linked_list.find_iterator()
    q = keys[RNG.integers(0, n, B)].astype(np.int32)
    ptr0, scr0 = it.init(jnp.asarray(q), head)
    ptr0 = jnp.asarray(np.where(np.arange(B) % 2 == 0, 10**6, np.asarray(ptr0)))
    mesh = mem_mesh(P)
    rec_f, _ = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=256, compact=True, schedule="fused"
    )
    rec_p, _ = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=256, compact=True,
        schedule="pipelined",
    )
    np.testing.assert_array_equal(rec_p, rec_f)
    from repro.core.iterator import STATUS_FAULT

    assert (rec_p[::2, routing.F_STATUS] == STATUS_FAULT).all()
    print("pipelined fault ok")


def check_fused_handles_faults():
    """Switch-level faults retire identically on the fused path."""
    n, B = 64, 16
    keys = np.arange(n, dtype=np.int32)
    values = RNG.integers(0, 100, n).astype(np.int32)
    ar, head = linked_list.build(keys, values, num_shards=P)
    it = linked_list.find_iterator()
    q = keys[RNG.integers(0, n, B)].astype(np.int32)
    ptr0, scr0 = it.init(jnp.asarray(q), head)
    ptr0 = jnp.asarray(np.where(np.arange(B) % 2 == 0, 10**6, np.asarray(ptr0)))
    mesh = mem_mesh(P)
    rec_d, _ = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=256, compact=True, fused=False
    )
    rec_f, _ = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh, max_iters=256, compact=True, fused=True
    )
    np.testing.assert_array_equal(rec_f, rec_d)
    from repro.core.iterator import STATUS_FAULT

    assert (rec_f[::2, routing.F_STATUS] == STATUS_FAULT).all()
    print("fused fault ok")


if __name__ == "__main__":
    assert jax.device_count() == P, jax.devices()
    check_compact_equals_uncompacted()
    check_compact_handles_faults()
    check_fused_equivalence_all_structures()
    check_fused_handles_faults()
    check_pipelined_equivalence_all_structures()
    check_pipelined_kernel_local_backend()
    check_pipelined_handles_faults()
    print("ALL COMPACTION CHECKS PASSED")
