"""Elasticity + replication service matrix (8 host devices, subprocess).

Covers the service-level contracts that need a real 8-shard mesh:

  * hot-shard replication under a mid-stream primary kill -- recovery is
    log-shipped, the replica stays bit-identical to the primary, and the
    mixed run matches the failure-free run bit-for-bit;
  * read fan-out -- a read-only workload rides out a primary kill with
    ZERO retries (no STATUS_RETRY ever surfaces to a read tenant);
  * watchdog escalation -- an attributable-delay straggler (no kill, so
    the positive ShardFailure signal never fires) is probed, suspected,
    and fanned around, again with zero read retries;
  * live 2x reshard -- a 4 -> 8 shard change mid-stream (sync + async
    pipelines, read-only + read-write) drains, cuts over, and finishes
    bit-identical to a cold run at 8 shards.

Run via ``tests/test_elastic.py`` (subprocess, own XLA device count) or
directly: ``PYTHONPATH=src python tests/helpers/elastic_checks.py``.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import tempfile

import numpy as np
import jax

from repro.core.arena import ArenaBuilder, remap_shards
from repro.distributed.sharding import mem_mesh
from repro.core.engine import PulseEngine
from repro.core.faults import FaultInjector, FaultPlan
from repro.core.iterator import STATUS_DONE
from repro.core.structures import bst, linked_list
from repro.distributed.arena_ft import (
    ArenaStore,
    FaultToleranceConfig,
    ReplicationConfig,
)
from repro.serving.admission import TraversalRequest
from repro.serving.traversal_service import PulseService, StructureSpec

P = 8
KEYS = np.arange(100, 164, dtype=np.int32)


def build_list():
    b = ArenaBuilder(512, 4, num_shards=P, policy="interleaved")
    head = linked_list.build_into(b, KEYS, KEYS * 2)
    return b.finish(), head


def make_reqs(n=36):
    reqs = []
    for i in range(n):
        if i % 4 == 2:
            reqs.append(TraversalRequest(
                i, "list_ins", 1000 + i, value=i * 11,
                tenant="w", arrive_round=i // 8,
            ))
        else:
            reqs.append(TraversalRequest(
                i, "list", int(KEYS[(i * 7) % len(KEYS)]),
                tenant="r", arrive_round=i // 8,
            ))
    return reqs


def serve_rep(tmp, plan, pipeline, *, dead_rounds=3, watchdog=0.0,
              reads_only=False):
    arena, head = build_list()
    inj = FaultInjector(plan) if plan is not None else None
    eng = PulseEngine(arena, mesh=mem_mesh(P),
                      fault_injector=inj)
    ft = FaultToleranceConfig(
        store=ArenaStore(tmp), snapshot_every=100, dead_rounds=dead_rounds,
        replication=ReplicationConfig(policy="failover"),
        watchdog_timeout_s=watchdog,
    )
    svc = PulseService(
        eng,
        {
            "list": StructureSpec(linked_list.find_iterator(), (head,),
                                  group="list"),
            "list_ins": StructureSpec(linked_list.insert_iterator(), (head,),
                                      group="list", takes_value=True),
        },
        slots_per_structure=8, quantum=6, pipeline=pipeline,
        fault_tolerance=ft,
    )
    reqs = make_reqs()
    if reads_only:
        reqs = [r for r in reqs if r.tenant == "r"]
    m = svc.run(reqs)
    rep = svc._replicas
    ft.store.close()
    return reqs, m, eng.arena, rep


def check_replication_failover(pipeline):
    """Mixed read/write workload, primary killed mid-stream: recovery +
    log-shipped replica, everything bit-identical to the clean run."""
    plan = FaultPlan(kill_shard=3, kill_call=4, kill_superstep=2)
    with tempfile.TemporaryDirectory() as d0, \
            tempfile.TemporaryDirectory() as d1:
        r0, m0, ar0, rep0 = serve_rep(d0, None, pipeline)
        r1, m1, ar1, rep1 = serve_rep(d1, plan, pipeline)
    tag = f"rep-failover/{pipeline}"
    assert m1.recoveries == 1, (tag, m1.recoveries)
    assert m1.replica_quanta > 0 and m0.replica_quanta > 0
    # read-only tenants: zero retries, all DONE
    for r in r1:
        if r.tenant == "r":
            assert r.status == STATUS_DONE, (tag, r.req_id, r.status)
            assert r.retries == 0, (tag, r.req_id, r.retries)
    assert m1.completed == m0.completed == 36, (tag, m1.completed)
    for a, b in zip(r0, r1):
        assert a.status == b.status, (tag, a.req_id)
        np.testing.assert_array_equal(a.result, b.result,
                                      err_msg=f"{tag}/{a.req_id}")
    np.testing.assert_array_equal(np.asarray(ar0.data), np.asarray(ar1.data),
                                  err_msg=tag)
    np.testing.assert_array_equal(np.asarray(ar0.heap), np.asarray(ar1.heap),
                                  err_msg=tag)
    # replica is still bit-identical to the primary after everything
    rep1.verify(ar1)
    print(f"{tag} ok: retries={m1.retries} recoveries={m1.recoveries} "
          f"replica_quanta={m1.replica_quanta}")


def check_readonly_zero_retry(pipeline):
    """Kill a primary while only read tenants are in flight: reads fan out
    to the replica with zero STATUS_RETRY / zero retries charged."""
    plan = FaultPlan(kill_shard=3, kill_call=4, kill_superstep=2)
    with tempfile.TemporaryDirectory() as d0, \
            tempfile.TemporaryDirectory() as d1:
        r0, m0, ar0, _ = serve_rep(d0, None, pipeline, reads_only=True)
        r1, m1, ar1, _ = serve_rep(d1, plan, pipeline, dead_rounds=6,
                                   reads_only=True)
    tag = f"rep-zero-retry/{pipeline}"
    assert m1.recoveries == 1, (tag, m1.recoveries)
    assert m1.failover_quanta >= 1, (tag, m1.failover_quanta)
    assert m1.retries == 0, (tag, m1.retries)
    assert m1.retry_exhausted == 0 and m1.shed == 0, tag
    for a, b in zip(r0, r1):
        assert a.status == b.status == STATUS_DONE, (tag, a.req_id, b.status)
        assert b.retries == 0, (tag, b.req_id)
        np.testing.assert_array_equal(a.result, b.result,
                                      err_msg=f"{tag}/{a.req_id}")
    np.testing.assert_array_equal(np.asarray(ar0.data), np.asarray(ar1.data),
                                  err_msg=tag)
    print(f"{tag} ok: failover_quanta={m1.failover_quanta} "
          f"completed={m1.completed}")


def check_watchdog_delay(pipeline):
    """Delay-only straggler (the fail-stop blind spot): the per-round
    watchdog probe escalates it to suspected-dead and reads fan out --
    no recovery, no retries, results identical to the clean run."""
    plan = FaultPlan(delay_shard=2, delay_s=0.15)
    with tempfile.TemporaryDirectory() as d0, \
            tempfile.TemporaryDirectory() as d1:
        r0, m0, ar0, _ = serve_rep(d0, None, pipeline, reads_only=True)
        r1, m1, ar1, _ = serve_rep(d1, plan, pipeline, dead_rounds=1000,
                                   watchdog=0.05, reads_only=True)
    tag = f"watchdog-delay/{pipeline}"
    assert m1.watchdog_probes > 0, tag
    assert m1.watchdog_suspects >= 1, (tag, m1.watchdog_suspects)
    assert m1.failover_quanta >= 1, (tag, m1.failover_quanta)
    assert m1.retries == 0 and m1.recoveries == 0, (tag, m1.retries)
    for a, b in zip(r0, r1):
        assert a.status == b.status == STATUS_DONE, (tag, a.req_id)
        np.testing.assert_array_equal(a.result, b.result,
                                      err_msg=f"{tag}/{a.req_id}")
    print(f"{tag} ok: suspects={m1.watchdog_suspects} "
          f"probes={m1.watchdog_probes} failover_quanta={m1.failover_quanta}")


# ------------------------------- resharding ---------------------------------


def build_bst4():
    b = ArenaBuilder(512, 4, num_shards=4, policy="interleaved")
    root, _h = bst.build_into(b, KEYS, KEYS * 2)
    return b.finish(), root


def bst_reqs(n=40, writes=True):
    # updates are alloc-free (bst.update_iterator), so the committed state
    # is partition-independent -- the cold-equivalence check stays exact
    reqs = []
    for i in range(n):
        if writes and i % 4 == 3:
            k = int(KEYS[(i * 5) % len(KEYS)])
            reqs.append(TraversalRequest(
                i, "bst_upd", k, value=9000 + i, tenant="w",
                arrive_round=i // 6,
            ))
        else:
            reqs.append(TraversalRequest(
                i, "bst", int(KEYS[(i * 7) % len(KEYS)]), tenant="r",
                arrive_round=i // 6,
            ))
    return reqs


def serve_reshard(arena, root, nshards, pipeline, *, reshard_at=None,
                  writes=True):
    mesh = mem_mesh(nshards)
    eng = PulseEngine(arena, mesh=mesh)
    svc = PulseService(
        eng,
        {
            "bst": StructureSpec(bst.find_iterator(), (root,), group="bst"),
            "bst_upd": StructureSpec(bst.update_iterator(), (root,),
                                     group="bst", takes_value=True),
        },
        slots_per_structure=8, quantum=6, pipeline=pipeline,
    )
    reqs = bst_reqs(writes=writes)
    for r in reqs:
        svc.submit(r)
    try:
        while svc._busy():
            if reshard_at is not None and svc.metrics.rounds == reshard_at:
                svc.request_reshard(8)
            if svc.metrics.rounds > 10000:
                raise RuntimeError("no drain")
            svc.step()
    finally:
        svc.close()
        svc._drain_emit()
    return reqs, svc.metrics, eng.arena


def check_live_reshard(pipeline, writes):
    """Mid-stream 4 -> 8 reshard vs a cold run at 8 shards (the cold arena
    is the offline ``remap_shards`` of the same 4-shard build, which is the
    partition the live path converges to)."""
    a4, root = build_bst4()
    cold8 = remap_shards(a4, 8)
    rc, mc, arc = serve_reshard(cold8, root, 8, pipeline, writes=writes)
    a4b, root_b = build_bst4()
    assert root_b == root
    rm, mm, arm = serve_reshard(a4b, root, 4, pipeline, reshard_at=3,
                                writes=writes)
    tag = f"reshard/{pipeline}/{'rw' if writes else 'ro'}"
    assert mm.reshards == 1, tag
    assert arm.num_shards == 8, tag
    for a, b in zip(rc, rm):
        assert a.status == b.status == STATUS_DONE, (tag, a.req_id, a.status,
                                                     b.status)
        np.testing.assert_array_equal(a.result, b.result,
                                      err_msg=f"{tag}/{a.req_id}")
    np.testing.assert_array_equal(np.asarray(arc.data), np.asarray(arm.data),
                                  err_msg=tag)
    np.testing.assert_array_equal(np.asarray(arc.bounds),
                                  np.asarray(arm.bounds), err_msg=tag)
    np.testing.assert_array_equal(np.asarray(arc.perms),
                                  np.asarray(arm.perms), err_msg=tag)
    # allocator registers match; epoch/commit counters are commit-placement
    # metadata and legitimately differ when early quanta committed at 4
    hc, hm = np.asarray(arc.heap), np.asarray(arm.heap)
    np.testing.assert_array_equal(hc[:, :2], hm[:, :2], err_msg=tag)
    if not writes:
        np.testing.assert_array_equal(hc, hm, err_msg=tag)
        assert mm.commits == mc.commits == 0
    else:
        assert mm.commits == mc.commits > 0, (tag, mm.commits, mc.commits)
    print(f"{tag} ok: drain_rounds={mm.reshard_drain_rounds} "
          f"commits={mm.commits} rounds {mc.rounds}->{mm.rounds}")


if __name__ == "__main__":
    assert jax.device_count() == P, jax.device_count()
    for pipe in ("sync", "async"):
        check_replication_failover(pipe)
        check_readonly_zero_retry(pipe)
        check_watchdog_delay(pipe)
        check_live_reshard(pipe, writes=False)
        check_live_reshard(pipe, writes=True)
    print("ALL ELASTICITY CHECKS PASSED")
