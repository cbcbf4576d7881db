"""Multi-device routing checks. Run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so in-process tests keep
seeing 1 device (per the dry-run isolation rule)."""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import routing  # noqa: E402
from repro.distributed.sharding import mem_mesh  # noqa: E402
from repro.core.engine import PulseEngine  # noqa: E402
from repro.core.iterator import STATUS_DONE, STATUS_FAULT, execute_batched  # noqa: E402
from repro.core.structures import btree, hash_table, linked_list  # noqa: E402

RNG = np.random.default_rng(11)
P = 8


def mesh():
    return mem_mesh(P)


def unique_keys(n, lo=0, hi=10**6):
    return RNG.choice(np.arange(lo, hi, dtype=np.int64), size=n, replace=False).astype(
        np.int32
    )


def check_btree_distributed_vs_oracle():
    """Distributed supersteps must equal the single-node executor exactly."""
    n = 4000
    keys = unique_keys(n)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, root, height = btree.build(keys, values, num_shards=P, policy="sequential")
    it = btree.find_iterator()
    queries = np.concatenate([keys[:256], unique_keys(256, hi=10**4)])
    ptr0, scr0 = it.init(jnp.asarray(queries), root)

    # oracle: single-device batched executor over the unsharded arena
    o_ptr, o_scr, o_status, o_iters = execute_batched(
        it, ar, ptr0, scr0, max_iters=64
    )

    rec, stats = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh(), axis_name="mem", max_iters=64, k_local=2
    )
    assert rec.shape[0] == queries.shape[0], "conservation: every request returns"
    np.testing.assert_array_equal(rec[:, routing.F_SCRATCH:], np.asarray(o_scr))
    np.testing.assert_array_equal(rec[:, routing.F_STATUS], np.asarray(o_status))
    np.testing.assert_array_equal(rec[:, routing.F_ITERS], np.asarray(o_iters))
    assert stats.crossings.max() >= 1, "multi-shard traversal must cross nodes"

    # compacted supersteps: identical results, strictly less fabric traffic
    rec_c, stats_c = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh(), axis_name="mem", max_iters=64, k_local=2,
        compact=True,
    )
    np.testing.assert_array_equal(rec_c[:, routing.F_SCRATCH:], np.asarray(o_scr))
    np.testing.assert_array_equal(rec_c[:, routing.F_STATUS], np.asarray(o_status))
    np.testing.assert_array_equal(rec_c[:, routing.F_ITERS], np.asarray(o_iters))
    assert stats_c.total_wire_words < stats.total_wire_words
    print(
        f"btree ok: supersteps={stats.supersteps} "
        f"mean_crossings={stats.crossings.mean():.2f} "
        f"wire compact/base={stats_c.total_wire_words}/{stats.total_wire_words}"
    )


def check_pulse_acc_matches_but_costs_more():
    """Fig. 9: PULSE-ACC returns identical results with ~2x crossings."""
    n = 2000
    keys = unique_keys(n)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, root, _ = btree.build(keys, values, num_shards=P, policy="interleaved")
    it = btree.find_iterator()
    queries = keys[:128]
    ptr0, scr0 = it.init(jnp.asarray(queries), root)
    rec_a, st_a = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh(), axis_name="mem", max_iters=64
    )
    rec_b, st_b = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh(), axis_name="mem", max_iters=64,
        return_to_cpu=True,
    )
    np.testing.assert_array_equal(rec_a[:, routing.F_SCRATCH:], rec_b[:, routing.F_SCRATCH:])
    assert st_b.crossings.sum() > st_a.crossings.sum(), (
        "PULSE-ACC must incur strictly more network crossings "
        f"({st_b.crossings.sum()} vs {st_a.crossings.sum()})"
    )
    print(
        f"pulse-acc ok: crossings {st_a.crossings.sum()} (switch) vs "
        f"{st_b.crossings.sum()} (via CPU node)"
    )


def check_hash_distributed():
    n, n_buckets = 3000, 256
    keys = unique_keys(n)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, heads = hash_table.build(keys, values, n_buckets, num_shards=P)
    it = hash_table.find_iterator(n_buckets)
    queries = np.concatenate([keys[:200], unique_keys(200, hi=10**4)])
    ptr0, scr0 = it.init(jnp.asarray(queries), jnp.asarray(heads))
    o = execute_batched(it, ar, ptr0, scr0, max_iters=256)
    rec, stats = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh(), axis_name="mem", max_iters=256
    )
    np.testing.assert_array_equal(rec[:, routing.F_SCRATCH:], np.asarray(o[1]))
    np.testing.assert_array_equal(rec[:, routing.F_STATUS], np.asarray(o[2]))
    print(f"hash ok: supersteps={stats.supersteps}")


def check_allocation_policy_effect():
    """Appendix Fig. 5: interleaved (uniform) allocation must cause more
    cross-node traversals than partitioned (sequential) allocation."""
    n = 4000
    keys = np.sort(unique_keys(n))
    values = RNG.integers(0, 1000, n).astype(np.int32)
    it = btree.find_iterator()
    crossings = {}
    for policy in ("sequential", "interleaved"):
        ar, root, _ = btree.build(keys, values, num_shards=P, policy=policy)
        queries = keys[RNG.integers(0, n, 256)]
        ptr0, scr0 = it.init(jnp.asarray(queries), root)
        rec, stats = routing.distributed_execute(
            it, ar, ptr0, scr0, mesh=mesh(), axis_name="mem", max_iters=64
        )
        crossings[policy] = stats.crossings.mean()
    assert crossings["interleaved"] > crossings["sequential"], crossings
    print(f"allocation ok: {crossings}")


def check_protection_fault_routes_home():
    """A traversal touching a no-read range must FAULT and return home."""
    keys = np.arange(64, dtype=np.int32)
    values = np.ones(64, np.int32)
    ar, head = linked_list.build(keys, values, num_shards=P)
    # revoke read on shard 4 (the chain passes through every shard)
    perms = np.asarray(ar.perms).copy()
    perms[4] = 0
    import dataclasses

    ar = dataclasses.replace(ar, perms=jnp.asarray(perms))
    it = linked_list.sum_iterator()
    ptr0, scr0 = it.init(jnp.asarray([head], jnp.int32))
    rec, stats = routing.distributed_execute(
        it, ar, ptr0, scr0, mesh=mesh(), axis_name="mem", max_iters=1000
    )
    assert int(rec[0, routing.F_STATUS]) == STATUS_FAULT
    # progressed through shards 0..3 (8 nodes per shard) then faulted
    assert int(rec[0, routing.F_SCRATCH + 1]) == 32, rec[0]
    print("protection ok")


def check_engine_front_door():
    n = 1000
    keys = unique_keys(n)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, root, _ = btree.build(keys, values, num_shards=P)
    eng = PulseEngine(ar, mesh=mesh(), axis_name="mem")
    it = btree.find_iterator()
    ptr0, scr0 = it.init(jnp.asarray(keys[:64]), root)
    res = eng.execute(it, ptr0, scr0, max_iters=64)
    assert res.offloaded
    assert (res.status == STATUS_DONE).all()
    assert (res.scratch[:, 2] == 1).all()  # all found
    print("engine ok")


if __name__ == "__main__":
    assert jax.device_count() == P, jax.devices()
    check_btree_distributed_vs_oracle()
    check_pulse_acc_matches_but_costs_more()
    check_hash_distributed()
    check_allocation_policy_effect()
    check_protection_fault_routes_home()
    check_engine_front_door()
    print("ALL DISTRIBUTED CHECKS PASSED")
