"""Write-path determinism checks (8 emulated devices -- the acceptance
configuration): every distributed schedule x fabric must match the
sequential-commit oracle bit for bit on records, supersteps, wire words,
AND final arena contents (data + heap registers)."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import commit, routing  # noqa: E402
from repro.distributed.sharding import mem_mesh  # noqa: E402
from repro.core.arena import PERM_READ, ArenaBuilder, make_arena  # noqa: E402
from repro.core.iterator import STATUS_DONE, STATUS_FAULT  # noqa: E402
from repro.core.structures import (  # noqa: E402
    bst,
    btree,
    hash_table,
    linked_list,
    skiplist,
)

RNG = np.random.default_rng(11)
P = 8

SCHEDULES = (
    ("dispatched", "dense"),
    ("fused", "dense"),
    ("fused", "ring"),
    ("pipelined", "dense"),
    ("pipelined", "ring"),
)


def _assert_matches_oracle(name, it, arena, p0, s0, *, max_iters):
    """Replay one pre-state through the oracle and every schedule x fabric."""
    rec_o, st_o, ar_o = commit.sequential_commit_execute(
        it, arena, p0, s0, max_iters=max_iters
    )
    mesh = mem_mesh(P)
    for schedule, fabric in SCHEDULES:
        rec_d, st_d, ar_d = routing.distributed_execute(
            it, arena, p0, s0, mesh=mesh, max_iters=max_iters,
            compact=True, schedule=schedule, fabric=fabric,
        )
        tag = f"{name}/{schedule}/{fabric}"
        np.testing.assert_array_equal(rec_d, rec_o, err_msg=tag)
        np.testing.assert_array_equal(
            np.asarray(ar_d.data), np.asarray(ar_o.data), err_msg=tag
        )
        np.testing.assert_array_equal(
            np.asarray(ar_d.heap), np.asarray(ar_o.heap), err_msg=tag
        )
        assert st_d.supersteps == st_o.supersteps, (tag, st_d, st_o)
        assert st_d.total_wire_words == st_o.total_wire_words, (tag, st_d, st_o)
        assert st_d.commits == st_o.commits, (tag, st_d.commits, st_o.commits)
        assert st_d.epochs == st_o.epochs, (tag, st_d.epochs, st_o.epochs)
    return rec_o, st_o, ar_o


def check_chain_mixed_rw():
    """Mixed find/insert/delete racing in ONE batch on an interleaved list."""
    n, B = 64, 48
    b = ArenaBuilder(256, 4, num_shards=P, policy="interleaved")
    keys = np.arange(10, 10 + n, dtype=np.int32)
    head = linked_list.build_into(b, keys, keys * 3)
    ar = b.finish()
    it = linked_list.rw_iterator()
    ops = np.tile([1, 0, 2, 0], B // 4).astype(np.int32)
    # victim discipline (per-node locks are future work): racing deletes must
    # not target list-adjacent nodes, the head, or the tail region where the
    # racing inserts CAS -- pick every 4th middle key
    del_keys = keys[4 : 4 + 4 * (B // 4) : 4]
    find_keys = keys[np.setdiff1d(RNG.permutation(n)[: B], np.arange(4, n, 4))][: B // 2]
    qk = np.empty(B, np.int32)
    qk[ops == 1] = np.arange(B // 4) + 1000  # fresh keys to insert
    qk[ops == 2] = del_keys[: B // 4]
    qk[ops == 0] = np.resize(find_keys, B // 2)
    qv = (np.arange(B) + 7).astype(np.int32)
    p0, s0 = it.init(ops, qk, qv, head)
    rec, st, ar_o = _assert_matches_oracle("list-rw", it, ar, p0, s0, max_iters=4096)
    assert (rec[:, routing.F_STATUS] == STATUS_DONE).all()
    assert st.commits > 0 and st.epochs > 0
    # every inserted key findable, every deleted key gone, on the final heap
    fit = linked_list.find_iterator()
    ins_keys = qk[ops == 1]
    del_keys = qk[ops == 2]
    fp, fs = fit.init(jnp.asarray(np.concatenate([ins_keys, del_keys])), head)
    from repro.core.iterator import execute_batched

    _, fscr, _, _ = execute_batched(fit, ar_o, fp, fs, max_iters=4096)
    fscr = np.asarray(fscr)
    assert (fscr[: len(ins_keys), 2] == 1).all(), "inserted keys must be findable"
    assert (fscr[len(ins_keys):, 2] == 0).all(), "deleted keys must be gone"
    print(
        f"chain mixed-rw ok: steps={st.supersteps} commits={st.commits} "
        f"epochs={st.epochs} wire={st.total_wire_words}"
    )


def check_hash_mixed_rw():
    """Mixed ops against the sentinel-headed writable hash table."""
    n, B, NB = 48, 32, 16
    b = ArenaBuilder(256, 4, num_shards=P, policy="interleaved")
    keys = RNG.choice(np.arange(100, 10_000), n, replace=False).astype(np.int32)
    sent = hash_table.build_writable(b, keys, keys + 1, NB)
    ar = b.finish()
    it = hash_table.rw_iterator(NB)
    ops = np.tile([1, 0, 2, 0], B // 4).astype(np.int32)
    # victim discipline: one delete per bucket (chain-adjacent victims race),
    # and inserts target buckets disjoint from the delete buckets (a racing
    # insert CASes its bucket's tail, which must not be getting freed)
    kb = hash_table._np_hash(keys, NB)
    del_keys, used = [], set()
    for k, bk in zip(keys, kb):
        if int(bk) not in used:
            del_keys.append(int(k))
            used.add(int(bk))
        if len(del_keys) == B // 4:
            break
    ins_keys = []
    cand = 20_000
    while len(ins_keys) < B // 4:
        if int(hash_table._np_hash(np.asarray([cand], np.int32), NB)[0]) not in used:
            ins_keys.append(cand)
        cand += 1
    find_keys = [int(k) for k in keys if int(k) not in set(del_keys)][: B // 2]
    qk = np.empty(B, np.int32)
    qk[ops == 1] = ins_keys
    qk[ops == 2] = del_keys
    qk[ops == 0] = np.resize(np.asarray(find_keys, np.int32), B // 2)
    qv = (np.arange(B) + 5).astype(np.int32)
    p0, s0 = it.init(ops, qk, qv, sent)
    rec, st, ar_o = _assert_matches_oracle("hash-rw", it, ar, p0, s0, max_iters=4096)
    assert (rec[:, routing.F_STATUS] == STATUS_DONE).all()
    fit = hash_table.find_iterator(NB)
    fp, fs = fit.init(jnp.asarray(qk[ops == 1]), jnp.asarray(sent))
    from repro.core.iterator import execute_batched

    _, fscr, _, _ = execute_batched(fit, ar_o, fp, fs, max_iters=4096)
    assert (np.asarray(fscr)[:, 2] == 1).all()
    print(f"hash mixed-rw ok: steps={st.supersteps} commits={st.commits}")


def check_skiplist_insert_delete():
    """Sequenced skiplist workload: racing inserts, then non-adjacent racing
    deletes, each phase replayed through every schedule vs the oracle."""
    n = 40
    b = ArenaBuilder(256, 12, num_shards=P, policy="interleaved")
    keys = np.sort(RNG.choice(np.arange(0, 5000, 2), n, replace=False)).astype(np.int32)
    head = skiplist.build_into(b, keys, keys * 2)
    ar = b.finish()
    newk = (keys[:16] + 1).astype(np.int32)  # odd keys: absent, never adjacent
    it = skiplist.insert_iterator()
    p0, s0 = it.init(jnp.asarray(newk), jnp.asarray(newk * 2), head)
    rec, st, ar1 = _assert_matches_oracle("skip-insert", it, ar, p0, s0, max_iters=4096)
    assert (rec[:, routing.F_STATUS] == STATUS_DONE).all()
    # delete every other inserted key (victims separated by surviving keys)
    vict = newk[::2]
    dit = skiplist.delete_iterator()
    dp, ds = dit.init(jnp.asarray(vict), head)
    rec2, st2, ar2 = _assert_matches_oracle("skip-delete", dit, ar1, dp, ds, max_iters=4096)
    assert (rec2[:, routing.F_SCRATCH + skiplist.SD_RES] == 1).all()
    fit = skiplist.find_iterator()
    fp, fs = fit.init(jnp.asarray(np.concatenate([keys, newk[1::2]])), head)
    from repro.core.iterator import execute_batched

    _, fscr, _, _ = execute_batched(fit, ar2, fp, fs, max_iters=4096)
    assert (np.asarray(fscr)[:, 2] == 1).all()
    fp, fs = fit.init(jnp.asarray(vict), head)
    _, fscr, _, _ = execute_batched(fit, ar2, fp, fs, max_iters=4096)
    assert (np.asarray(fscr)[:, 2] == 0).all()
    print(
        f"skiplist insert+delete ok: commits={st.commits}+{st2.commits} "
        f"steps={st.supersteps}/{st2.supersteps}"
    )


def check_tree_updates():
    """bst + btree update-in-place, including racing writers to one key."""
    n = 96
    keys = np.sort(RNG.choice(np.arange(10**5), n, replace=False)).astype(np.int32)
    vals = np.arange(n, dtype=np.int32)
    for name, mod, W in (("bst", bst, 4), ("btree", btree, 20)):
        b = ArenaBuilder(256, W, num_shards=P, policy="interleaved")
        root, _ = mod.build_into(b, keys, vals)
        ar = b.finish()
        it = mod.update_iterator()
        # 24 updates; three writers race on keys[0] -- the commit order's
        # (slot, id) serialization decides the survivor deterministically
        q = np.concatenate([[keys[0]] * 3, keys[1:20], keys[-2:]]).astype(np.int32)
        nv = (np.arange(len(q)) + 9000).astype(np.int32)
        p0, s0 = it.init(jnp.asarray(q), jnp.asarray(nv), root)
        rec, st, ar_o = _assert_matches_oracle(
            f"{name}-update", it, ar, p0, s0, max_iters=1024
        )
        assert (rec[:, routing.F_SCRATCH + mod.U_FOUND] == 1).all()
        fit = mod.find_iterator()
        fp, fs = fit.init(jnp.asarray(q[3:]), root)
        from repro.core.iterator import execute_batched

        _, fscr, fstatus, _ = execute_batched(fit, ar_o, fp, fs, max_iters=1024)
        if name == "bst":
            value, found = mod.result(jnp.asarray(fscr))
            np.testing.assert_array_equal(np.asarray(value), nv[3:])
        else:
            np.testing.assert_array_equal(np.asarray(fscr)[:, 1], nv[3:])
        print(f"{name} update ok: steps={st.supersteps} commits={st.commits}")


def check_write_permission_fault():
    """Commits on a PERM_WRITE-revoked shard must FAULT, identically on the
    oracle and every schedule."""
    n = 32
    b = ArenaBuilder(128, 4, num_shards=P, policy="interleaved")
    keys = np.arange(10, 10 + n, dtype=np.int32)
    head = linked_list.build_into(b, keys, keys)
    data = b.data.copy()
    heap = np.asarray(b.finish().heap)
    # revoke write on every shard: all ALLOC commits (home shards) fault
    perms = [PERM_READ] * P
    ar = make_arena(data, num_shards=P, perms=perms, heap=heap)
    it = linked_list.insert_iterator()
    p0, s0 = it.init(np.arange(8, dtype=np.int32) + 500, np.arange(8, dtype=np.int32), head)
    rec, st, ar_o = _assert_matches_oracle("perm-fault", it, ar, p0, s0, max_iters=512)
    assert (rec[:, routing.F_STATUS] == STATUS_FAULT).all()
    np.testing.assert_array_equal(np.asarray(ar_o.data), data)  # nothing written
    assert st.commits == 0
    print("write-permission fault ok")


def check_alloc_exhaustion_faults():
    """ALLOC on a full arena faults the record instead of clobbering rows."""
    n = 16
    cap = ((n + P - 1) // P) * P  # arena exactly full after the build
    b = ArenaBuilder(cap, 4, num_shards=P, policy="interleaved")
    keys = np.arange(10, 10 + n, dtype=np.int32)
    head = linked_list.build_into(b, keys, keys)
    ar = b.finish()
    it = linked_list.insert_iterator()
    p0, s0 = it.init(np.arange(4, dtype=np.int32) + 900, np.arange(4, dtype=np.int32), head)
    rec, st, ar_o = _assert_matches_oracle("alloc-exhaust", it, ar, p0, s0, max_iters=512)
    assert (rec[:, routing.F_STATUS] == STATUS_FAULT).all()
    np.testing.assert_array_equal(np.asarray(ar_o.data), np.asarray(ar.data))
    print("alloc exhaustion fault ok")


def check_service_async_sync_identity():
    """PulseService over the 8-shard mesh serving a mixed read/write quantum
    stream: the async device-runner pipeline must match the synchronous loop
    bit for bit on results, commits, and the final arena (data + heap).
    ALLOC addresses depend on write-batch composition, so this also pins the
    admission schedule itself."""
    from repro.core.engine import PulseEngine  # noqa: E402
    from repro.serving.admission import TraversalRequest  # noqa: E402
    from repro.serving.traversal_service import (  # noqa: E402
        PulseService,
        StructureSpec,
    )

    keys = np.arange(100, 164, dtype=np.int32)

    def serve(pipeline):
        b = ArenaBuilder(512, 4, num_shards=P, policy="interleaved")
        head = linked_list.build_into(b, keys, keys * 2)
        eng = PulseEngine(b.finish(), mesh=mem_mesh(P))
        svc = PulseService(
            eng,
            {
                "list": StructureSpec(
                    linked_list.find_iterator(), (head,), group="list"
                ),
                "list_ins": StructureSpec(
                    linked_list.insert_iterator(), (head,), group="list",
                    takes_value=True,
                ),
            },
            slots_per_structure=8,
            quantum=6,
            pipeline=pipeline,
        )
        reqs = []
        for i in range(36):
            if i % 4 == 2:
                reqs.append(
                    TraversalRequest(
                        i, "list_ins", 1000 + i, value=i * 11,
                        tenant="w", arrive_round=i // 8,
                    )
                )
            else:
                reqs.append(
                    TraversalRequest(
                        i, "list", int(keys[(i * 7) % len(keys)]),
                        tenant="r", arrive_round=i // 8,
                    )
                )
        m = svc.run(reqs)
        return reqs, m, eng.arena

    ra, ma, ar_a = serve("sync")
    rb, mb, ar_b = serve("async")
    assert ma.rounds == mb.rounds, (ma.rounds, mb.rounds)
    assert ma.engine_calls == mb.engine_calls
    assert ma.commits == mb.commits and ma.commits > 0, (ma.commits, mb.commits)
    assert ma.writes_retired == mb.writes_retired == 9
    for a, b_ in zip(ra, rb):
        assert (a.status, a.iters, a.finish_round) == (
            b_.status, b_.iters, b_.finish_round,
        ), a.req_id
        np.testing.assert_array_equal(a.result, b_.result, err_msg=str(a.req_id))
    np.testing.assert_array_equal(np.asarray(ar_a.data), np.asarray(ar_b.data))
    np.testing.assert_array_equal(np.asarray(ar_a.heap), np.asarray(ar_b.heap))
    print(
        f"service async/sync identity ok: rounds={ma.rounds} "
        f"commits={ma.commits} retired={ma.retired}"
    )


def check_service_chaos_recovery():
    """Kill a shard mid-stream under the full serving stack (8-shard mesh,
    mixed read/write, snapshots + commit log): after snapshot-restore + log
    replay + in-flight re-execution, the final arena AND every request's
    (status, result) must be bit-identical to the failure-free run -- zero
    acknowledged commits lost."""
    import tempfile

    from repro.core.engine import PulseEngine  # noqa: E402
    from repro.core.faults import FaultInjector, FaultPlan  # noqa: E402
    from repro.distributed.arena_ft import (  # noqa: E402
        ArenaStore,
        FaultToleranceConfig,
    )
    from repro.serving.admission import TraversalRequest  # noqa: E402
    from repro.serving.traversal_service import (  # noqa: E402
        PulseService,
        StructureSpec,
    )

    keys = np.arange(100, 164, dtype=np.int32)

    def serve(tmp, plan, pipeline):
        b = ArenaBuilder(512, 4, num_shards=P, policy="interleaved")
        head = linked_list.build_into(b, keys, keys * 2)
        inj = FaultInjector(plan) if plan is not None else None
        eng = PulseEngine(
            b.finish(), mesh=mem_mesh(P), fault_injector=inj
        )
        # baseline-only snapshots (cadence larger than the workload): every
        # acknowledged write quantum sits in the commit log, so recovery
        # MUST replay -- replayed_commits > 0 is then deterministic, not a
        # kill-point/snapshot-cadence alignment accident
        ft = FaultToleranceConfig(store=ArenaStore(tmp), snapshot_every=100)
        svc = PulseService(
            eng,
            {
                "list": StructureSpec(
                    linked_list.find_iterator(), (head,), group="list"
                ),
                "list_ins": StructureSpec(
                    linked_list.insert_iterator(), (head,), group="list",
                    takes_value=True,
                ),
            },
            slots_per_structure=8,
            quantum=6,
            pipeline=pipeline,
            fault_tolerance=ft,
        )
        reqs = []
        for i in range(36):
            if i % 4 == 2:
                reqs.append(
                    TraversalRequest(
                        i, "list_ins", 1000 + i, value=i * 11,
                        tenant="w", arrive_round=i // 8,
                    )
                )
            else:
                reqs.append(
                    TraversalRequest(
                        i, "list", int(keys[(i * 7) % len(keys)]),
                        tenant="r", arrive_round=i // 8,
                    )
                )
        m = svc.run(reqs)
        ft.store.close()
        return reqs, m, eng.arena

    # kill late enough that acknowledged commits sit in the log past the
    # latest snapshot: recovery must actually replay them (replayed > 0)
    plan = FaultPlan(kill_shard=3, kill_call=30, kill_superstep=2)
    for pipeline in ("sync", "async"):
        with tempfile.TemporaryDirectory() as d0, \
                tempfile.TemporaryDirectory() as d1:
            r0, m0, ar0 = serve(d0, None, pipeline)
            r1, m1, ar1 = serve(d1, plan, pipeline)
            tag = f"chaos/{pipeline}"
            assert m1.recoveries == 1, (tag, m1.recoveries)
            assert m1.retries > 0, tag
            assert m1.replayed_commits > 0, tag
            assert m0.recoveries == 0 and m0.retries == 0
            assert m1.completed == m0.completed == 36, tag
            assert m1.commits == m0.commits and m1.commits > 0, tag
            for a, b_ in zip(r0, r1):
                assert a.status == b_.status, (tag, a.req_id)
                np.testing.assert_array_equal(
                    a.result, b_.result, err_msg=f"{tag}/{a.req_id}"
                )
            np.testing.assert_array_equal(
                np.asarray(ar0.data), np.asarray(ar1.data), err_msg=tag
            )
            np.testing.assert_array_equal(
                np.asarray(ar0.heap), np.asarray(ar1.heap), err_msg=tag
            )
            print(
                f"service chaos recovery ok ({pipeline}): recoveries=1 "
                f"retries={m1.retries} replayed={m1.replayed_commits} "
                f"mean_recovery={m1.mean_recovery_ms:.0f}ms"
            )


if __name__ == "__main__":
    assert jax.device_count() == P, jax.devices()
    check_chain_mixed_rw()
    check_hash_mixed_rw()
    check_skiplist_insert_delete()
    check_tree_updates()
    check_write_permission_fault()
    check_alloc_exhaustion_faults()
    check_service_async_sync_identity()
    check_service_chaos_recovery()
    print("ALL WRITE-PATH CHECKS PASSED")
