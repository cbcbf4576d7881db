"""Fault-injection checks on the 8-shard mesh (the acceptance configuration):
every schedule x fabric must (a) die cleanly on an injected shard kill --
ShardFailure raised, input arena untouched, a clean rerun still matches the
oracle -- and (b) under fabric loss, park-and-retransmit until the final
records are bit-identical to the loss-free run."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import commit, routing  # noqa: E402
from repro.distributed.sharding import mem_mesh  # noqa: E402
from repro.core.arena import ArenaBuilder  # noqa: E402
from repro.core.faults import FaultInjector, FaultPlan, ShardFailure  # noqa: E402
from repro.core.iterator import STATUS_DONE  # noqa: E402
from repro.core.structures import linked_list  # noqa: E402

RNG = np.random.default_rng(23)
P = 8

SCHEDULES = (
    ("dispatched", "dense"),
    ("fused", "dense"),
    ("fused", "ring"),
    ("pipelined", "dense"),
    ("pipelined", "ring"),
)


def _build():
    n = 64
    b = ArenaBuilder(512, 4, num_shards=P, policy="interleaved")
    keys = np.arange(10, 10 + n, dtype=np.int32)
    head = linked_list.build_into(b, keys, keys * 3)
    return b.finish(), head, keys


def check_kill_every_schedule():
    """A targeted shard kill raises ShardFailure on every schedule x fabric
    *without* publishing partial state, and a clean rerun of the same
    pre-state still matches the oracle bit for bit."""
    arena, head, _ = _build()
    data0 = np.asarray(arena.data).copy()
    heap0 = np.asarray(arena.heap).copy()
    it = linked_list.insert_iterator()
    newk = (np.arange(16, dtype=np.int32) + 900)
    p0, s0 = it.init(jnp.asarray(newk), jnp.asarray(newk * 2), head)
    rec_o, st_o, ar_o = commit.sequential_commit_execute(
        it, arena, p0, s0, max_iters=4096
    )
    mesh = mem_mesh(P)
    for schedule, fabric in SCHEDULES:
        inj = FaultInjector(FaultPlan(kill_shard=2, kill_superstep=3))
        try:
            routing.distributed_execute(
                it, arena, p0, s0, mesh=mesh, max_iters=4096,
                compact=True, schedule=schedule, fabric=fabric,
                fault_injector=inj,
            )
            raise AssertionError(f"{schedule}/{fabric}: kill did not fire")
        except ShardFailure as e:
            assert (e.shard, e.superstep) == (2, 3), (schedule, fabric, e)
        tag = f"kill/{schedule}/{fabric}"
        # the input arena is untouched: nothing partial was published
        np.testing.assert_array_equal(np.asarray(arena.data), data0, err_msg=tag)
        np.testing.assert_array_equal(np.asarray(arena.heap), heap0, err_msg=tag)
        # the same pre-state replays cleanly to the oracle's answer
        rec_d, st_d, ar_d = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=4096,
            compact=True, schedule=schedule, fabric=fabric,
        )
        np.testing.assert_array_equal(rec_d, rec_o, err_msg=tag)
        np.testing.assert_array_equal(
            np.asarray(ar_d.data), np.asarray(ar_o.data), err_msg=tag
        )
        np.testing.assert_array_equal(
            np.asarray(ar_d.heap), np.asarray(ar_o.heap), err_msg=tag
        )
        assert st_d.commits == st_o.commits
        print(f"{tag} ok (died before superstep 3, clean rerun matches oracle)")


def check_kill_superstep_counting():
    """kill_superstep is 1-based fire-before: killing at superstep 1 means
    zero supersteps ran; a kill past the run's natural length never fires."""
    arena, head, keys = _build()
    it = linked_list.find_iterator()
    p0, s0 = it.init(jnp.asarray(keys[:16]), head)
    mesh = mem_mesh(P)
    rec_ref, st_ref = routing.distributed_execute(
        it, arena, p0, s0, mesh=mesh, max_iters=4096,
        compact=True, schedule="dispatched", fabric="dense",
    )
    for schedule in ("dispatched", "fused"):
        inj = FaultInjector(FaultPlan(kill_shard=0, kill_superstep=1))
        try:
            routing.distributed_execute(
                it, arena, p0, s0, mesh=mesh, max_iters=4096,
                compact=True, schedule=schedule, fabric="dense",
                fault_injector=inj,
            )
            raise AssertionError(f"{schedule}: superstep-1 kill did not fire")
        except ShardFailure as e:
            assert e.superstep == 1
        # a kill scheduled after completion is unreachable: run finishes
        inj = FaultInjector(
            FaultPlan(kill_shard=0, kill_superstep=st_ref.supersteps + 1)
        )
        rec, st = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=4096,
            compact=True, schedule=schedule, fabric="dense",
            fault_injector=inj,
        )
        assert not inj.fired
        np.testing.assert_array_equal(rec, rec_ref, err_msg=schedule)
    print("kill superstep counting ok (1-based, fire-before semantics)")


def check_drop_retransmit_identity():
    """Fabric loss (park-and-retransmit) must not change any final record:
    dropped records retry until they cross, so only superstep counts grow."""
    arena, head, keys = _build()
    it = linked_list.find_iterator()
    q = keys[RNG.permutation(len(keys))[:32]]
    p0, s0 = it.init(jnp.asarray(q), head)
    mesh = mem_mesh(P)
    for schedule, fabric in SCHEDULES:
        rec_ref, st_ref = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=4096,
            compact=True, schedule=schedule, fabric=fabric,
        )
        inj = FaultInjector(FaultPlan(drop_prob=0.4, drop_seed=7))
        rec, st = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=4096,
            compact=True, schedule=schedule, fabric=fabric,
            fault_injector=inj,
        )
        tag = f"drop/{schedule}/{fabric}"
        np.testing.assert_array_equal(rec, rec_ref, err_msg=tag)
        assert (rec[:, routing.F_STATUS] == STATUS_DONE).all(), tag
        assert st.supersteps >= st_ref.supersteps, (tag, st.supersteps)
        # replays are deterministic: same seed -> same superstep count
        inj2 = FaultInjector(FaultPlan(drop_prob=0.4, drop_seed=7))
        rec2, st2 = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=4096,
            compact=True, schedule=schedule, fabric=fabric,
            fault_injector=inj2,
        )
        np.testing.assert_array_equal(rec2, rec, err_msg=tag)
        assert st2.supersteps == st.supersteps, tag
        print(
            f"{tag} ok: supersteps {st_ref.supersteps} -> {st.supersteps}, "
            f"records identical"
        )


def check_drop_write_path_validity():
    """Loss under the *write* path: delaying a record's crossing legally
    shifts which commit superstep it lands in, so the exact serialization
    (ALLOC addresses, CAS retry counts) may differ from the loss-free run --
    but the result must still be a *valid* one (every insert lands, every
    inserted key findable) and the seeded loss mask makes it exactly
    replayable."""
    from repro.core.iterator import execute_batched

    arena, head, _ = _build()
    it = linked_list.insert_iterator()
    newk = (np.arange(12, dtype=np.int32) + 700)
    p0, s0 = it.init(jnp.asarray(newk), jnp.asarray(newk + 1), head)
    mesh = mem_mesh(P)
    for schedule, fabric in (("dispatched", "dense"), ("pipelined", "ring")):
        inj = FaultInjector(FaultPlan(drop_prob=0.3, drop_seed=3))
        rec, st, ar = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=4096,
            compact=True, schedule=schedule, fabric=fabric,
            fault_injector=inj,
        )
        tag = f"drop-write/{schedule}/{fabric}"
        assert (rec[:, routing.F_STATUS] == STATUS_DONE).all(), tag
        assert st.commits > 0, tag
        fit = linked_list.find_iterator()
        fp, fs = fit.init(jnp.asarray(newk), head)
        _, fscr, _, _ = execute_batched(fit, ar, fp, fs, max_iters=4096)
        assert (np.asarray(fscr)[:, 2] == 1).all(), tag
        # seeded loss replays bit-identically (records AND final arena)
        inj2 = FaultInjector(FaultPlan(drop_prob=0.3, drop_seed=3))
        rec2, st2, ar2 = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=4096,
            compact=True, schedule=schedule, fabric=fabric,
            fault_injector=inj2,
        )
        np.testing.assert_array_equal(rec2, rec, err_msg=tag)
        np.testing.assert_array_equal(
            np.asarray(ar2.data), np.asarray(ar.data), err_msg=tag
        )
        np.testing.assert_array_equal(
            np.asarray(ar2.heap), np.asarray(ar.heap), err_msg=tag
        )
        assert st2.commits == st.commits, tag
        print(
            f"{tag} ok: commits={st.commits}, all inserts landed, "
            f"replay bit-identical"
        )


def check_delay_identity():
    """A straggler shard (dispatched path) slows the run but changes no
    result -- delay is purely temporal.  The delay is *attributable*: the
    straggler sleeps only before supersteps in which it actually serves
    work, so the slowdown is at least one delay period but (unlike the old
    every-superstep model) not necessarily supersteps * delay."""
    import time

    arena, head, keys = _build()
    it = linked_list.find_iterator()
    p0, s0 = it.init(jnp.asarray(keys[:16]), head)
    mesh = mem_mesh(P)
    rec_ref, st_ref = routing.distributed_execute(
        it, arena, p0, s0, mesh=mesh, max_iters=4096,
        compact=True, schedule="dispatched", fabric="dense",
    )
    inj = FaultInjector(FaultPlan(delay_shard=1, delay_s=0.02))
    t0 = time.perf_counter()
    rec, st = routing.distributed_execute(
        it, arena, p0, s0, mesh=mesh, max_iters=4096,
        compact=True, schedule="dispatched", fabric="dense",
        fault_injector=inj,
    )
    dt = time.perf_counter() - t0
    np.testing.assert_array_equal(rec, rec_ref)
    assert st.supersteps == st_ref.supersteps
    # shard 1 serves work in at least one superstep of a 16-key find
    assert dt >= 0.02, (dt, st.supersteps)
    print(f"delay identity ok: {st.supersteps} supersteps, {dt * 1e3:.0f}ms")


def check_replica_fanout_matrix():
    """Routing-level replica fan-out, every dead-primary case: with R=2
    replication, reads keep completing when any single primary is dead, and
    the payload fields (status, iters, scratch, ptr) match the failure-free
    run exactly -- only hops/supersteps may shift (records are served
    elsewhere, their state trajectory never changes).  The replicated
    sequential-commit oracle must match the device run bit-for-bit."""
    arena, head, keys = _build()
    it = linked_list.find_iterator()
    q = keys[RNG.permutation(len(keys))[:32]]
    p0, s0 = it.init(jnp.asarray(q), head)
    mesh = mem_mesh(P)
    plan = routing.make_replica_plan(P, policy="failover")
    data = np.asarray(arena.data)
    bounds = np.asarray(arena.bounds)
    rep_rows = np.zeros_like(data)
    for holder, p in enumerate(plan.primary_map):
        if p >= 0:
            rep_rows[bounds[holder]:bounds[holder + 1]] = (
                data[bounds[p]:bounds[p + 1]]
            )
    rec_ref, _ = routing.distributed_execute(
        it, arena, p0, s0, mesh=mesh, max_iters=4096,
        compact=True, schedule="dispatched", fabric="dense",
    )
    payload = [routing.F_ID, routing.F_PTR, routing.F_STATUS, routing.F_ITERS]
    for dead in range(P):
        mask = np.zeros(P, bool)
        mask[dead] = True
        ctx = routing.ReplicaContext(plan=plan, rep_rows=rep_rows, dead_mask=mask)
        rec, st = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=4096,
            compact=True, schedule="dispatched", fabric="dense",
            replication=ctx,
        )
        tag = f"fanout/dead={dead}"
        rec_np = np.asarray(rec)
        ref_np = np.asarray(rec_ref)
        np.testing.assert_array_equal(
            rec_np[:, payload], ref_np[:, payload], err_msg=tag
        )
        np.testing.assert_array_equal(
            rec_np[:, routing.F_SCRATCH:], ref_np[:, routing.F_SCRATCH:],
            err_msg=tag,
        )
        assert (rec_np[:, routing.F_STATUS] == STATUS_DONE).all(), tag
        # replicated oracle: bit-identical including hops + supersteps
        rec_o, st_o = commit.sequential_commit_execute(
            it, arena, p0, s0, max_iters=4096, k_local=4, compact=True,
            replication=ctx,
        )
        np.testing.assert_array_equal(rec_np, np.asarray(rec_o), err_msg=tag)
        assert st.supersteps == st_o.supersteps, (tag, st.supersteps)
    print(f"replica fan-out matrix ok: {P} dead-primary cases, payload "
          f"identical, oracle bit-identical")


def check_replication_service_matrix():
    """Service-level 8-shard kill matrix: for every shard, kill it mid-
    stream under the full serving stack with R=2 replication on and assert
    (a) the hot standby is bit-identical to the primary after every write
    quantum (verify_every_quantum raises on any divergence), (b) read-only
    tenants complete with zero STATUS_RETRY and zero retries charged while
    the primary is dead, and (c) post-recovery primary == replica ==
    durable oracle (snapshot + commit-log replay)."""
    import tempfile

    from repro.core.engine import PulseEngine  # noqa: E402
    from repro.distributed.arena_ft import (  # noqa: E402
        ArenaStore,
        FaultToleranceConfig,
        ReplicationConfig,
    )
    from repro.serving.admission import TraversalRequest  # noqa: E402
    from repro.serving.traversal_service import (  # noqa: E402
        PulseService,
        StructureSpec,
    )

    keys = np.arange(100, 164, dtype=np.int32)

    def serve(tmp, plan, *, reads_only=False, dead_rounds=6):
        b = ArenaBuilder(512, 4, num_shards=P, policy="interleaved")
        head = linked_list.build_into(b, keys, keys * 2)
        inj = FaultInjector(plan) if plan is not None else None
        eng = PulseEngine(
            b.finish(), mesh=mem_mesh(P), fault_injector=inj
        )
        ft = FaultToleranceConfig(
            store=ArenaStore(tmp), snapshot_every=100, dead_rounds=dead_rounds,
            replication=ReplicationConfig(policy="failover"),
        )
        specs = {
            "list": StructureSpec(
                linked_list.find_iterator(), (head,), group="list"
            ),
        }
        if not reads_only:
            specs["list_ins"] = StructureSpec(
                linked_list.insert_iterator(), (head,), group="list",
                takes_value=True,
            )
        svc = PulseService(
            eng, specs, slots_per_structure=8, quantum=6,
            fault_tolerance=ft,
        )
        reqs = []
        for i in range(36):
            if not reads_only and i % 4 == 2:
                reqs.append(TraversalRequest(
                    i, "list_ins", 1000 + i, value=i * 11,
                    tenant="w", arrive_round=i // 8,
                ))
            else:
                reqs.append(TraversalRequest(
                    i, "list", int(keys[(i * 7) % len(keys)]),
                    tenant="r", arrive_round=i // 8,
                ))
        m = svc.run(reqs)
        rep = svc._replicas
        recovered, _info = ft.store.recover()
        ft.store.close()
        return reqs, m, eng.arena, rep, recovered

    with tempfile.TemporaryDirectory() as d:
        ref_r, ref_m, ref_ar, _, _ = serve(d, None, reads_only=True)
    for dead in range(P):
        plan = FaultPlan(kill_shard=dead, kill_call=4, kill_superstep=2)
        with tempfile.TemporaryDirectory() as d:
            r1, m1, ar1, rep1, rec1 = serve(d, plan, reads_only=True)
        tag = f"svc-kill/read-only/shard={dead}"
        assert m1.recoveries == 1, (tag, m1.recoveries)
        assert m1.failover_quanta >= 1, (tag, m1.failover_quanta)
        assert m1.retries == 0 and m1.retry_exhausted == 0, (tag, m1.retries)
        for a, b_ in zip(ref_r, r1):
            assert a.status == b_.status == STATUS_DONE, (tag, a.req_id)
            assert b_.retries == 0, (tag, b_.req_id)
            np.testing.assert_array_equal(
                a.result, b_.result, err_msg=f"{tag}/{a.req_id}"
            )
        np.testing.assert_array_equal(
            np.asarray(ref_ar.data), np.asarray(ar1.data), err_msg=tag
        )
    print(f"svc kill matrix (read-only) ok: {P} shards, zero STATUS_RETRY, "
          f"results identical")

    with tempfile.TemporaryDirectory() as d:
        w_r, w_m, w_ar, w_rep, w_rec = serve(d, None)
    assert w_m.replica_quanta > 0
    for dead in range(P):
        plan = FaultPlan(kill_shard=dead, kill_call=4, kill_superstep=2)
        with tempfile.TemporaryDirectory() as d:
            r1, m1, ar1, rep1, rec1 = serve(d, plan)
        tag = f"svc-kill/mixed/shard={dead}"
        assert m1.recoveries == 1, (tag, m1.recoveries)
        # (a) held throughout: verify_every_quantum raises on divergence
        assert m1.replica_quanta > 0, tag
        # (b) reads never charged a retry, never retired STATUS_RETRY
        for b_ in r1:
            if b_.tenant == "r":
                assert b_.status == STATUS_DONE and b_.retries == 0, (
                    tag, b_.req_id, b_.status, b_.retries,
                )
        # results + final arena bit-identical to the failure-free run
        assert m1.completed == w_m.completed == 36, (tag, m1.completed)
        for a, b_ in zip(w_r, r1):
            assert a.status == b_.status, (tag, a.req_id)
            np.testing.assert_array_equal(
                a.result, b_.result, err_msg=f"{tag}/{a.req_id}"
            )
        np.testing.assert_array_equal(
            np.asarray(w_ar.data), np.asarray(ar1.data), err_msg=tag
        )
        np.testing.assert_array_equal(
            np.asarray(w_ar.heap), np.asarray(ar1.heap), err_msg=tag
        )
        # (c) primary == replica == durable oracle, post-recovery
        rep1.verify(ar1)
        for field in ("data", "bounds", "perms", "heap"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ar1, field)),
                np.asarray(getattr(rec1, field)),
                err_msg=f"{tag}/oracle.{field}",
            )
    print(f"svc kill matrix (mixed r/w) ok: {P} shards, replica verified "
          f"per quantum, primary == replica == oracle")


if __name__ == "__main__":
    assert jax.device_count() == P, jax.devices()
    check_kill_every_schedule()
    check_kill_superstep_counting()
    check_drop_retransmit_identity()
    check_drop_write_path_validity()
    check_delay_identity()
    check_replica_fanout_matrix()
    check_replication_service_matrix()
    print("ALL FAULT-INJECTION CHECKS PASSED")
