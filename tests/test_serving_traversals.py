"""PulseService serving layer: admission, fairness, continuations, compacted
supersteps, and the variable-depth pulse_chase wave scheduler."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.arena import NULL, ArenaBuilder
from repro.core.engine import PulseEngine
from repro.core.iterator import STATUS_DONE, execute_batched
from repro.core.structures import btree, hash_table, linked_list, skiplist
from repro.serving.admission import AdmissionController, TraversalRequest
from repro.serving.traversal_service import PulseService, ServiceMetrics, StructureSpec

ROOT = Path(__file__).resolve().parents[1]
RNG = np.random.default_rng(123)


# ------------------------------- admission -----------------------------------


def _req(rid, structure="s", tenant="t", deadline_ms=None):
    return TraversalRequest(rid, structure, query=rid, tenant=tenant, deadline_ms=deadline_ms)


def test_admission_preserves_fifo_within_tenant():
    ac = AdmissionController()
    for i in range(6):
        ac.submit(_req(i, tenant="a"), now_s=float(i))
    got = [r.req_id for r in ac.admit({"s": 4})]
    assert got == [0, 1, 2, 3]
    got = [r.req_id for r in ac.admit({"s": 4})]
    assert got == [4, 5]
    assert ac.pending() == 0


def test_admission_edf_across_tenants():
    ac = AdmissionController()
    ac.submit(_req(0, tenant="lazy"), now_s=0.0)  # no deadline
    ac.submit(_req(1, tenant="urgent", deadline_ms=10.0), now_s=0.0)
    ac.submit(_req(2, tenant="soon", deadline_ms=100.0), now_s=0.0)
    got = [r.req_id for r in ac.admit({"s": 3})]
    assert got == [1, 2, 0]  # earliest deadline first; best-effort last


def test_admission_fairness_no_starvation():
    """A flooding tenant must not starve a trickle tenant (credits alternate
    service when no deadlines differentiate)."""
    ac = AdmissionController()
    for i in range(20):
        ac.submit(_req(i, tenant="flood"), now_s=0.0)
    for i in range(20, 24):
        ac.submit(_req(i, tenant="trickle"), now_s=0.0)
    admitted = [ac.admit({"s": 2}) for _ in range(4)]
    tenants_per_round = [[r.tenant for r in batch] for batch in admitted]
    # every admission round serves both tenants while the trickle has work
    for round_tenants in tenants_per_round:
        assert set(round_tenants) == {"flood", "trickle"}, tenants_per_round


def test_admission_respects_per_structure_capacity():
    ac = AdmissionController()
    ac.submit(TraversalRequest(0, "full", 0, tenant="a"), now_s=0.0)
    ac.submit(TraversalRequest(1, "free", 1, tenant="b"), now_s=0.0)
    got = [r.req_id for r in ac.admit({"full": 0, "free": 1})]
    assert got == [1]
    assert ac.pending() == 1  # the blocked head keeps its queue position


def test_admission_serializes_writes_to_one_key():
    """Two writes to one key never share a batch; a write whose key is in
    flight waits, and blocks its tenant's later requests behind it."""
    ac = AdmissionController()
    for rid, (s, q) in enumerate([("w", 7), ("w", 8), ("w", 7), ("r", 7)]):
        ac.submit(TraversalRequest(rid, s, q, tenant="a"), now_s=0.0)

    def key(r):
        return ("g", r.query) if r.structure == "w" else None

    assert [r.req_id for r in ac.admit({"w": 4, "r": 4}, key)] == [0, 1]
    assert ac.admit({"w": 4, "r": 4}, key, busy_keys={("g", 7)}) == []
    assert [r.req_id for r in ac.admit({"w": 4, "r": 4}, key)] == [2, 3]


# ----------------------------- service loop ----------------------------------


def _mixed_service(slots=8, quantum=4, backend="xla", seed=9):
    n = 128
    rng = np.random.default_rng(seed)
    b = ArenaBuilder(2048, 20)
    lkeys = np.arange(n, dtype=np.int32)
    lvals = rng.integers(0, 10**6, n).astype(np.int32)
    head = linked_list.build_into(b, lkeys, lvals)
    bkeys = rng.choice(np.arange(10**4, 10**5), n, replace=False).astype(np.int32)
    bvals = rng.integers(0, 10**6, n).astype(np.int32)
    root, _ = btree.build_into(b, bkeys, bvals)
    hkeys = rng.choice(np.arange(10**5, 2 * 10**5), n, replace=False).astype(np.int32)
    hvals = rng.integers(0, 10**6, n).astype(np.int32)
    heads = hash_table.build_into(b, hkeys, hvals, 32)
    skeys = rng.choice(np.arange(2 * 10**5, 3 * 10**5), n, replace=False).astype(np.int32)
    svals = rng.integers(0, 10**6, n).astype(np.int32)
    shead = skiplist.build_into(b, skeys, svals)
    svc = PulseService(
        PulseEngine(b.finish()),
        {
            "list": StructureSpec(linked_list.find_iterator(), (head,)),
            "btree": StructureSpec(btree.find_iterator(), (root,)),
            "hash": StructureSpec(hash_table.find_iterator(32), (jnp.asarray(heads),)),
            "skip": StructureSpec(skiplist.find_iterator(), (shead,)),
        },
        slots_per_structure=slots,
        quantum=quantum,
        backend=backend,
    )
    data = {
        "list": (lkeys, lvals),
        "btree": (bkeys, bvals),
        "hash": (hkeys, hvals),
        "skip": (skeys, svals),
    }
    return svc, data


def test_service_mixed_workload_end_to_end():
    svc, data = _mixed_service()
    reqs = []
    rid = 0
    for s, (keys, _) in data.items():
        for _ in range(12):
            reqs.append(TraversalRequest(rid, s, int(keys[RNG.integers(0, len(keys))])))
            rid += 1
        reqs.append(TraversalRequest(rid, s, 5 * 10**6))  # guaranteed miss
        rid += 1
    m = svc.run(reqs)
    assert m.completed == len(reqs)
    assert np.isfinite(m.p50_ms) and np.isfinite(m.p99_ms)
    assert m.throughput_rps > 0
    for r in reqs:
        keys, values = data[r.structure]
        hit = r.query in keys
        found = bool(r.result[2])  # every find iterator reports [_, value, found]
        assert found == hit, (r.structure, r.query, r.result)
        if hit and r.structure != "btree":
            assert r.result[1] == values[list(keys).index(r.query)]


def test_service_mixed_read_write_tenants():
    """Write path end to end through the service: a write tenant's inserts
    commit under the per-group barrier, and a read tenant's finds observe
    them once the barrier releases the group."""
    from repro.core.structures import hash_table

    b = ArenaBuilder(512, 4)
    keys = np.arange(100, 132, dtype=np.int32)
    head = linked_list.build_into(b, keys, keys * 2)
    sent = hash_table.build_writable(
        b, np.arange(200, 216, dtype=np.int32), np.arange(16, dtype=np.int32), 8
    )
    svc = PulseService(
        PulseEngine(b.finish()),
        {
            "list": StructureSpec(linked_list.find_iterator(), (head,), group="list"),
            "list_ins": StructureSpec(
                linked_list.insert_iterator(), (head,), group="list",
                takes_value=True,
            ),
            "list_del": StructureSpec(
                linked_list.delete_iterator(), (head,), group="list"
            ),
            "hash": StructureSpec(
                hash_table.find_iterator(8), (jnp.asarray(sent),), group="hash"
            ),
            "hash_ins": StructureSpec(
                hash_table.insert_iterator(8), (sent,), group="hash",
                takes_value=True,
            ),
        },
        slots_per_structure=8,
        quantum=8,
    )
    assert svc.groups["list_ins"].spec.writes and not svc.groups["list"].spec.writes
    reqs, rid = [], 0
    for k in range(300, 308):
        reqs.append(TraversalRequest(rid, "list_ins", query=k, value=k * 3, tenant="w"))
        rid += 1
    for k in [104, 110, 300, 305]:
        reqs.append(TraversalRequest(rid, "list", query=k, tenant="r"))
        rid += 1
    for k in [106, 115]:  # non-adjacent victims (head key 100 is the sentinel)
        reqs.append(TraversalRequest(rid, "list_del", query=int(k), tenant="w"))
        rid += 1
    for k in range(400, 406):
        reqs.append(TraversalRequest(rid, "hash_ins", query=k, value=k + 9, tenant="w"))
        rid += 1
    for k in [400, 403, 205]:
        reqs.append(TraversalRequest(rid, "hash", query=k, tenant="r"))
        rid += 1
    m = svc.run(reqs)
    assert m.completed == len(reqs)
    assert m.commits > 0 and m.writes_retired == 16
    for r in reqs:
        if r.structure == "list" and r.query >= 300:
            assert r.result[1] == r.query * 3  # find scratch: [key, value, found]
        if r.structure == "hash" and r.query >= 400:
            assert r.result[1] == r.query + 9
    # deletes took effect: a fresh find through the engine's updated arena
    fit = linked_list.find_iterator()
    p0, s0 = fit.init(jnp.asarray(np.array([106, 115], np.int32)), head)
    _, scr, _, _ = execute_batched(
        fit, svc.engine.arena, p0, s0, max_iters=4096
    )
    assert (np.asarray(scr)[:, 2] == 0).all()


def test_write_barrier_excludes_concurrent_readers():
    """While a write slot-group of a structure group is occupied, reads of
    that group are not admitted (and vice versa); other groups are free."""
    from repro.serving.admission import apply_write_barriers

    group_of = {"list": "list", "list_ins": "list", "hash": "hash"}
    writes = {"list": False, "list_ins": True, "hash": False}
    # writer occupied -> reads of 'list' blocked, 'hash' untouched
    free = apply_write_barriers(
        {"list": 4, "list_ins": 4, "hash": 4}, group_of, writes,
        {"list": False, "list_ins": True, "hash": False}, {},
    )
    assert free == {"list": 0, "list_ins": 4, "hash": 4}
    # readers occupied -> writer blocked
    free = apply_write_barriers(
        {"list": 4, "list_ins": 4, "hash": 4}, group_of, writes,
        {"list": True, "list_ins": False, "hash": False}, {},
    )
    assert free == {"list": 4, "list_ins": 0, "hash": 4}
    # queued writer drains readers out (anti-starvation)
    free = apply_write_barriers(
        {"list": 4, "list_ins": 4, "hash": 4}, group_of, writes,
        {"list": False, "list_ins": False, "hash": False}, {"list_ins": 2},
    )
    assert free == {"list": 0, "list_ins": 4, "hash": 4}
    # two writers of one group both pending: exactly ONE wins the round --
    # the one whose queued request arrived first (seq order, FIFO-consistent)
    group_of2 = {**group_of, "list_del": "list"}
    writes2 = {**writes, "list_del": True}
    free = apply_write_barriers(
        {"list": 4, "list_ins": 4, "list_del": 4, "hash": 4},
        group_of2, writes2,
        {n: False for n in group_of2}, {"list_ins": 0, "list_del": 5},
    )
    assert free == {"list": 0, "list_ins": 4, "list_del": 0, "hash": 4}
    # an occupied writer keeps the group against a pending rival
    free = apply_write_barriers(
        {"list": 4, "list_ins": 4, "list_del": 4, "hash": 4},
        group_of2, writes2,
        {"list": False, "list_ins": True, "list_del": False, "hash": False},
        {"list_del": 2},
    )
    assert free == {"list": 0, "list_ins": 4, "list_del": 0, "hash": 4}


def test_service_continuations_preempt_long_walks():
    """quantum << walk depth: deep list walks must span several rounds as
    MAXED continuations yet finish with exact hop counts."""
    svc, data = _mixed_service(slots=4, quantum=4)
    lkeys, lvals = data["list"]
    deep = int(lkeys[-1])  # deepest key: ~128 hops at quantum 4
    shallow = int(lkeys[2])
    reqs = [
        TraversalRequest(0, "list", deep),
        TraversalRequest(1, "list", shallow),
    ]
    m = svc.run(reqs)
    assert m.completed == 2
    r_deep, r_shallow = reqs
    assert r_deep.status == STATUS_DONE and bool(r_deep.result[2])
    assert r_deep.finish_round - r_deep.admit_round >= 2  # resumed repeatedly
    assert r_shallow.finish_round <= r_deep.finish_round
    assert r_deep.iters == len(lkeys) - 1 + 1  # hops to reach the deepest key
    # early retirement freed the shallow slot long before the deep one
    assert r_shallow.iters < r_deep.iters


def test_service_backfills_retired_slots():
    """More requests than slots: retirement must backfill so everything
    completes, and occupancy never exceeds the slot budget."""
    svc, data = _mixed_service(slots=2, quantum=8)
    lkeys, _ = data["list"]
    reqs = [
        TraversalRequest(i, "list", int(lkeys[RNG.integers(0, 32)]))
        for i in range(11)
    ]
    m = svc.run(reqs)
    assert m.completed == 11
    assert m.rounds > 1  # could not have fit in one round with 2 slots


def test_service_tenant_fairness_under_flood():
    svc, data = _mixed_service(slots=2, quantum=64)
    lkeys, _ = data["list"]
    reqs = [
        TraversalRequest(i, "list", int(lkeys[RNG.integers(0, 16)]), tenant="flood")
        for i in range(12)
    ] + [
        TraversalRequest(100 + i, "list", int(lkeys[RNG.integers(0, 16)]), tenant="trickle")
        for i in range(3)
    ]
    m = svc.run(reqs)
    assert m.per_tenant["trickle"]["completed"] == 3
    # the trickle tenant's requests all finish before the flood drains
    trickle_done = max(r.finish_round for r in reqs if r.tenant == "trickle")
    flood_done = max(r.finish_round for r in reqs if r.tenant == "flood")
    assert trickle_done < flood_done


def test_service_refuses_kernel_backend_for_isa_iterators():
    """The compiled kernel runs a read iterator's body in lane-columnar
    form, which the ISA VM (a while loop over a program table) has not: the
    service refuses such a structure on backend="kernel" at registration,
    before anything is enqueued, and still accepts it on XLA."""
    from repro.core import isa
    from repro.core.structures import isa_programs

    keys = np.arange(16, dtype=np.int32)
    ar, head = linked_list.build(keys, keys + 100)
    spec = {"lst": StructureSpec(isa.as_pulse_iterator(isa_programs.list_find_program()), (head,))}
    with pytest.raises(NotImplementedError, match="'lst' cannot be served with backend='kernel'"):
        PulseService(PulseEngine(ar), spec, backend="kernel")
    assert "lst" in PulseService(PulseEngine(ar), spec).groups


def test_service_kernel_backend_matches_xla():
    svc_x, data = _mixed_service(slots=4, quantum=16)
    svc_k, _ = _mixed_service(slots=4, quantum=16, backend="kernel")
    lkeys, lvals = data["list"]
    qs = [int(lkeys[i]) for i in (3, 17, 60)]
    rx = [TraversalRequest(i, "list", q) for i, q in enumerate(qs)]
    rk = [TraversalRequest(i, "list", q) for i, q in enumerate(qs)]
    svc_x.run(rx)
    svc_k.run(rk)
    for a, b in zip(rx, rk):
        np.testing.assert_array_equal(a.result, b.result)


# --------------------- variable-depth wave scheduler -------------------------


def test_pulse_chase_waves_matches_fixed_depth():
    from repro.kernels.pulse_chase import ops

    keys = RNG.choice(np.arange(10**5), size=256, replace=False).astype(np.int32)
    values = RNG.integers(0, 10**6, 256).astype(np.int32)
    ar, heads = hash_table.build(keys, values, 8)  # long skewed chains
    it = hash_table.find_iterator(8)
    q = np.concatenate([keys[:24], RNG.integers(10**5, 10**6, 8).astype(np.int32)])
    ptr0, scr0 = it.init(jnp.asarray(q), jnp.asarray(heads))
    st0 = jnp.zeros(32, jnp.int32)
    logic = ops.iterator_logic(it)
    MAX = 64
    r_ref = ops.pulse_chase(
        ar.data, ptr0, scr0, st0, logic_fn=logic, num_steps=MAX, use_pallas=False
    )
    p, s, st, stats = ops.pulse_chase_waves(
        ar.data, ptr0, scr0, st0,
        logic_fn=logic, max_steps=MAX, depth_quantum=8, wave=8, interpret=True,
    )
    np.testing.assert_array_equal(p, np.asarray(r_ref[0]))
    np.testing.assert_array_equal(s, np.asarray(r_ref[1]))
    np.testing.assert_array_equal(st, np.asarray(r_ref[2]))
    # skewed chains -> early lanes retire -> strictly less issued work
    assert stats.savings > 0.2, stats
    assert stats.lanes_per_chunk == sorted(stats.lanes_per_chunk, reverse=True)
    assert stats.retire_step.max() <= MAX


def test_pulse_chase_waves_null_entry_retires_immediately():
    from repro.kernels.pulse_chase import ops

    keys = np.arange(16, dtype=np.int32)
    values = np.arange(16, dtype=np.int32)
    ar, head = linked_list.build(keys, values)
    it = linked_list.find_iterator()
    ptr0, scr0 = it.init(jnp.asarray(keys[:8]), head)
    ptr0 = jnp.asarray(np.where(np.arange(8) < 4, NULL, np.asarray(ptr0)))
    logic = ops.iterator_logic(it)
    p, s, st, stats = ops.pulse_chase_waves(
        ar.data, ptr0, scr0, jnp.zeros(8, jnp.int32),
        logic_fn=logic, max_steps=32, wave=8, interpret=True,
    )
    assert (st == 1).all()
    assert (stats.retire_step[:4] == 0).all()  # never entered a chunk
    np.testing.assert_array_equal(np.asarray(s)[:4, 1], np.zeros(4))  # untouched scratch


def test_engine_kernel_backend_fault_parity():
    """A mid-walk NULL dereference must report STATUS_FAULT on both the XLA
    executor and the kernel wave scheduler, never a successful DONE."""
    from repro.core.iterator import STATUS_FAULT, PulseIterator

    keys = np.arange(32, dtype=np.int32)
    values = np.arange(100, 132, dtype=np.int32)
    ar, head = linked_list.build(keys, values)

    # a "blind" find that only terminates on a hit: a missing key walks off
    # the tail into NULL (the fault path under test)
    def next_fn(node, ptr, scratch):
        return node[2], scratch

    def end_fn(node, ptr, scratch):
        hit = node[0] == scratch[0]
        return hit, scratch.at[1].set(jnp.where(hit, node[1], scratch[1]))

    def init(qs, head_ptr):
        s = jnp.zeros((qs.shape[0], 2), jnp.int32).at[:, 0].set(qs)
        return jnp.full((qs.shape[0],), head_ptr, jnp.int32), s

    it = PulseIterator(2, next_fn, end_fn, init, name="blind_find")
    eng = PulseEngine(ar)
    ptr0, scr0 = it.init(jnp.asarray([5, 10**6], jnp.int32), head)  # hit, miss
    res_x = eng.execute(it, ptr0, scr0, max_iters=64, backend="xla")
    res_k = eng.execute(it, ptr0, scr0, max_iters=64, backend="kernel")
    assert res_x.status[0] == STATUS_DONE and res_k.status[0] == STATUS_DONE
    assert res_x.status[1] == STATUS_FAULT and res_k.status[1] == STATUS_FAULT
    np.testing.assert_array_equal(res_x.scratch, res_k.scratch)


def test_engine_kernel_backend_translation_faults():
    """Out-of-range pointers and perm-revoked ranges must FAULT on the
    kernel backend (quantum-granular fault_fn), not chase clamped garbage."""
    import dataclasses as dc

    from repro.core.arena import PERM_WRITE
    from repro.core.iterator import STATUS_FAULT

    keys = np.arange(16, dtype=np.int32)
    ar, head = linked_list.build(keys, keys * 2)
    it = linked_list.find_iterator()
    eng = PulseEngine(ar)
    ptr0, scr0 = it.init(jnp.asarray([3, 7], jnp.int32), head)
    ptr0 = jnp.asarray(np.array([10**6, int(np.asarray(ptr0)[1])], np.int32))
    res = eng.execute(it, ptr0, scr0, max_iters=64, backend="kernel")
    assert res.status[0] == STATUS_FAULT
    assert res.status[1] == STATUS_DONE and res.scratch[1][2] == 1

    ar2 = dc.replace(ar, perms=jnp.asarray([PERM_WRITE], jnp.int32))  # no READ
    ptr0b, scr0b = it.init(jnp.asarray([3], jnp.int32), head)
    res2 = PulseEngine(ar2).execute(it, ptr0b, scr0b, max_iters=64, backend="kernel")
    assert res2.status[0] == STATUS_FAULT


def test_engine_kernel_backend_matches_executor():
    n = 128
    keys = RNG.choice(np.arange(10**5), size=n, replace=False).astype(np.int32)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, root, height = btree.build(keys, values)
    it = btree.find_iterator()
    q = np.concatenate([keys[:16], RNG.integers(10**5, 10**6, 16).astype(np.int32)])
    ptr0, scr0 = it.init(jnp.asarray(q), root)
    eng = PulseEngine(ar)
    o = execute_batched(it, ar, ptr0, scr0, max_iters=64)
    res = eng.execute(it, ptr0, scr0, max_iters=64, backend="kernel")
    np.testing.assert_array_equal(res.ptr, np.asarray(o[0]))
    np.testing.assert_array_equal(res.scratch, np.asarray(o[1]))
    assert (res.status == STATUS_DONE).all()


# --------------------- compacted supersteps (multi-device) -------------------


def test_compacted_supersteps_subprocess():
    """Equivalence + wire-reduction checks need >1 XLA device, so they run in
    a subprocess with their own XLA_FLAGS (same isolation rule as
    test_distributed_routing)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "helpers" / "compaction_checks.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL COMPACTION CHECKS PASSED" in proc.stdout


def test_service_counts_quanta_by_execution_path():
    """Every quantum records where it ran; quanta the dispatch cost model
    sends to the host traversal (cpu_node_execute) are counted, so a run
    can prove none left the device path."""
    keys = np.arange(64, dtype=np.int32)
    ar, heads = hash_table.build(keys, keys * 5, 8)
    spec = {"hash": StructureSpec(hash_table.find_iterator(8), (jnp.asarray(heads),))}
    reqs = lambda: [TraversalRequest(i, "hash", query=int(k)) for i, k in enumerate(keys[:12])]

    on_device = PulseService(PulseEngine(ar), spec, slots_per_structure=8)
    m = on_device.run(reqs())
    assert m.completed == 12 and m.cpu_node_quanta == 0
    assert set(m.quanta_by_path) == {"xla"}
    assert m.quanta_by_path["xla"] == m.engine_calls

    # eta=0: no iterator fits under the offload threshold -> host traversal
    on_host = PulseService(PulseEngine(ar, eta=0.0), spec, slots_per_structure=8)
    m = on_host.run(reqs())
    assert m.completed == 12
    assert m.cpu_node_quanta == m.engine_calls == m.quanta_by_path["cpu_node"] > 0
