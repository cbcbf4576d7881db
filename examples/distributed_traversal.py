"""Distributed pointer traversals across 8 memory nodes (paper S5).

Range queries on a B+tree whose nodes are range-partitioned across an
8-shard mesh; in-flight requests are routed between shards by the switch
superstep (all_to_all), never bouncing through the CPU node.  Also runs the
PULSE-ACC ablation (Fig. 9) showing the extra crossings.

Needs 8 XLA host devices, so it re-execs itself with XLA_FLAGS if needed.
Run: PYTHONPATH=src python examples/distributed_traversal.py
"""

import os
import sys

if os.environ.get("_PULSE_EXAMPLE_CHILD") != "1":
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["_PULSE_EXAMPLE_CHILD"] = "1"
    os.execve(sys.executable, [sys.executable] + sys.argv, env)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import routing  # noqa: E402
from repro.distributed.sharding import mem_mesh  # noqa: E402
from repro.core.structures import btree  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

P = 8
rng = np.random.default_rng(1)
mesh = mem_mesh(P)
print(f"mesh: {P} memory nodes")

# time-ordered keys (the BTrDB shape), partitioned allocation
n = 50_000
keys = np.arange(n, dtype=np.int32)
values = rng.integers(0, 1000, n).astype(np.int32)
arena, root, height = btree.build(keys, values, num_shards=P, policy="sequential")
print(f"b+tree: {n} keys, height {height}, arena sharded {P} ways "
      f"(switch table = {np.asarray(arena.bounds)})")

# stateful range aggregations (sum/min/max/count in the scratch pad)
it = btree.range_aggregate_iterator()
lo = rng.integers(0, n - 2048, 64).astype(np.int32)
hi = (lo + 2048).astype(np.int32)
ptr0, scr0 = it.init(jnp.asarray(lo), jnp.asarray(hi), root)

rec, stats = routing.distributed_execute(
    it, arena, ptr0, scr0, mesh=mesh, axis_name="mem", max_iters=4096, k_local=8,
)
print(f"switch-routed: {stats.supersteps} supersteps, "
      f"mean crossings/request {stats.crossings.mean():.2f}")

# verify against the oracle
ref = btree.ref_range_aggregate(keys, values, lo, hi)
for i, (s, mn, mx, c) in enumerate(ref):
    got = rec[i, routing.F_SCRATCH:]
    assert int(got[btree.RA_SUM]) % 2**32 == s and int(got[btree.RA_COUNT]) == c
print("results match the single-node oracle exactly")

# PULSE-ACC ablation (Fig. 9): crossings bounce via the home node
rec2, stats2 = routing.distributed_execute(
    it, arena, ptr0, scr0, mesh=mesh, axis_name="mem", max_iters=4096,
    k_local=8, return_to_cpu=True,
)
np.testing.assert_array_equal(rec[:, routing.F_SCRATCH:], rec2[:, routing.F_SCRATCH:])
print(f"PULSE-ACC: identical results, {stats2.crossings.sum()} crossings vs "
      f"{stats.crossings.sum()} with in-network routing "
      f"({stats2.crossings.sum() / max(stats.crossings.sum(), 1):.2f}x)")
