"""pulse-verify specialization checks on an 8-shard mesh.

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so in-process tests keep seeing 1 device (per the dry-run isolation rule).

The acceptance gate for the analysis-driven hot-path specialization: a
verified read-only ISA program runs with the per-hop access-table probe
elided (and without mutation record lanes), and the results are
bit-identical to

  * the unspecialized distributed path (``elide_access_check=False``),
  * the single-device batched oracle (``iterator.execute_batched``),
  * the sequential-commit oracle (``commit.sequential_commit_execute``),

across dispatched/fused/pipelined schedules x dense/ring fabrics.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import isa, routing  # noqa: E402
from repro.distributed.sharding import mem_mesh  # noqa: E402
from repro.core.commit import sequential_commit_execute  # noqa: E402
from repro.core.iterator import execute_batched  # noqa: E402
from repro.core.routing import F_ID, F_ITERS, F_PTR, F_SCRATCH, F_STATUS  # noqa: E402
from repro.core.structures import isa_programs, linked_list  # noqa: E402

RNG = np.random.default_rng(23)
P = 8
# payload columns per the bit-identity protocol: F_HOME/F_HOPS are routing
# metadata and may differ across schedules; everything else must match
PAYLOAD = [F_ID, F_PTR, F_STATUS, F_ITERS]


def mesh():
    return mem_mesh(P)


def payload(rec, S):
    rec = np.asarray(rec)
    return np.concatenate(
        [rec[:, PAYLOAD], rec[:, F_SCRATCH : F_SCRATCH + S]], axis=1
    )


def build_list(n=400):
    keys = RNG.choice(np.arange(0, 10**6), size=n, replace=False).astype(np.int32)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, head = linked_list.build(keys, values, num_shards=P)
    queries = np.concatenate(
        [keys[:: max(1, n // 64)][:64], RNG.integers(0, 10**4, 64).astype(np.int32)]
    )
    ptr0, scr0 = linked_list.find_iterator().init(jnp.asarray(queries), head)
    return ar, ptr0, scr0


def check_readonly_specialization_bit_identity():
    """Elided vs unspecialized vs both oracles, all schedules x fabrics."""
    ar, ptr0, scr0 = build_list()
    vm = isa.as_pulse_iterator(isa_programs.list_find_program())
    S = vm.scratch_words
    assert vm.facts is not None and vm.facts.read_only
    assert routing.can_elide_access_check(vm, ar)

    o_ptr, o_scr, o_status, o_iters = execute_batched(
        vm, ar, ptr0, scr0, max_iters=1024
    )
    rec_sc, _ = sequential_commit_execute(
        vm, ar, ptr0, scr0, max_iters=1024, k_local=4, compact=True
    )
    base = payload(rec_sc, S)
    np.testing.assert_array_equal(base[:, 1], np.asarray(o_ptr))
    np.testing.assert_array_equal(base[:, 2], np.asarray(o_status))
    np.testing.assert_array_equal(base[:, 3], np.asarray(o_iters))
    np.testing.assert_array_equal(base[:, 4:], np.asarray(o_scr))

    m = mesh()
    for sched in ("dispatched", "fused", "pipelined"):
        for fabric in ("dense", "ring"):
            rec_e, st_e = routing.distributed_execute(
                vm, ar, ptr0, scr0, mesh=m, axis_name="mem", max_iters=1024,
                k_local=4, compact=True, schedule=sched, fabric=fabric,
            )
            rec_u, st_u = routing.distributed_execute(
                vm, ar, ptr0, scr0, mesh=m, axis_name="mem", max_iters=1024,
                k_local=4, compact=True, schedule=sched, fabric=fabric,
                elide_access_check=False,
            )
            np.testing.assert_array_equal(np.asarray(rec_e), np.asarray(rec_u))
            np.testing.assert_array_equal(payload(rec_e, S), base)
            assert st_e.supersteps == st_u.supersteps
            assert st_e.total_wire_words == st_u.total_wire_words
            print(f"  {sched}/{fabric}: bit-identical "
                  f"({st_e.supersteps} supersteps)")
    print("readonly specialization bit-identity: PASS")


def check_dead_store_lane_skip():
    """A dead store-class op must not force the mutating record format.

    ``verify=False`` (the conservative ``Program.mutates`` opcode scan)
    routes the dead-store variant down the write path -- wider records on
    every fabric crossing, write barriers armed -- yet the store never
    executes, so results match the verified read path exactly.  The wire
    gap IS the lane-skip saving; pulse-verify itself rejects the variant
    (dead code), pointing at the dead store.
    """
    from repro.core.verify import E_UNREACHABLE, VerifyError, verify_program

    prog = isa_programs.list_find_program()
    dead = isa.Program(
        code=np.vstack([prog.code, [[isa.STOREN, 2, 0, 1]]]),
        scratch_words=prog.scratch_words,
        node_words=prog.node_words,
        name="list_find_dead_store",
    )
    assert dead.mutates  # the conservative opcode scan over-approximates
    try:
        verify_program(dead)
        raise AssertionError("dead-store program must be rejected")
    except VerifyError as e:
        assert E_UNREACHABLE in e.codes
        assert any(d.pc == len(dead) - 1 for d in e.diagnostics)

    ar, ptr0, scr0 = build_list(200)
    vm_ro = isa.as_pulse_iterator(prog)
    vm_rw = isa.as_pulse_iterator(dead, verify=False)
    assert not vm_ro.mutates and vm_rw.mutates
    S = vm_ro.scratch_words

    m = mesh()
    rec_ro, st_ro = routing.distributed_execute(
        vm_ro, ar, ptr0, scr0, mesh=m, axis_name="mem", max_iters=1024,
        k_local=4, compact=True, schedule="fused",
    )
    rec_rw, st_rw, ar_rw = routing.distributed_execute(
        vm_rw, ar, ptr0, scr0, mesh=m, axis_name="mem", max_iters=1024,
        k_local=4, compact=True, schedule="fused",
    )
    np.testing.assert_array_equal(payload(rec_ro, S), payload(rec_rw, S))
    np.testing.assert_array_equal(np.asarray(ar_rw.data), np.asarray(ar.data))
    assert st_ro.total_wire_words < st_rw.total_wire_words, (
        st_ro.total_wire_words, st_rw.total_wire_words,
    )
    saved = 1 - st_ro.total_wire_words / st_rw.total_wire_words
    print(f"dead-store lane skip: PASS (wire words -{saved:.0%})")


def check_elision_refused_when_unprovable():
    """No certificate, revoked perms, or a mutating program => no elision."""
    from repro.core.arena import PERM_WRITE

    ar, _, _ = build_list(100)
    vm = isa.as_pulse_iterator(isa_programs.list_find_program())
    traced = linked_list.find_iterator()  # hand-written JAX: facts is None
    assert not routing.can_elide_access_check(traced, ar)
    unverified = isa.as_pulse_iterator(
        isa_programs.list_find_program(), verify=False
    )
    assert not routing.can_elide_access_check(unverified, ar)
    mut = isa.as_pulse_iterator(isa_programs.bst_update_program())
    assert not routing.can_elide_access_check(mut, ar)
    # revoke PERM_READ on one shard: the probe is no longer constant-true
    import dataclasses as _dc

    perms = np.asarray(ar.perms).copy()
    perms[3] = PERM_WRITE
    ar_revoked = _dc.replace(ar, perms=jnp.asarray(perms))
    assert not routing.can_elide_access_check(vm, ar_revoked)
    assert routing.can_elide_access_check(vm, ar)
    print("elision refusal (no proof): PASS")


if __name__ == "__main__":
    check_elision_refused_when_unprovable()
    check_dead_store_lane_skip()
    check_readonly_specialization_bit_identity()
    print("ALL VERIFY SPECIALIZATION CHECKS PASSED")
