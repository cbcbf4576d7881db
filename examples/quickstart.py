"""Quickstart: the PULSE core in 60 lines.

Builds a hash table in a disaggregated arena, expresses ``find`` as a PULSE
iterator (init/next/end + scratch pad), lets the dispatch engine decide
offload (t_c <= eta * t_d), and runs a batch of lookups through the
accelerator executor -- including a continuation (max-iteration) resume.

Run: PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax.numpy as jnp

from repro.core import PulseEngine, STATUS_DONE, STATUS_MAXED
from repro.core.iterator import execute_batched, resume
from repro.core.structures import hash_table
from repro.compile_cache import use_compile_cache

use_compile_cache()

rng = np.random.default_rng(0)

# 1) build a bucket-chained hash table in the arena (the "memory node" heap)
n_keys, n_buckets = 20_000, 256
keys = rng.choice(np.arange(10**6), size=n_keys, replace=False).astype(np.int32)
values = rng.integers(0, 10**6, n_keys).astype(np.int32)
arena, bucket_heads = hash_table.build(keys, values, n_buckets)
print(f"arena: {arena.capacity} nodes x {arena.node_words} words "
      f"({arena.node_words * 4} B/record, single aggregated LOAD)")

# 2) the traversal as a PULSE iterator
it = hash_table.find_iterator(n_buckets)

# 3) dispatch decision: is this memory-bound enough to offload?
engine = PulseEngine(arena)
decision = engine.dispatch(it)
print(f"dispatch: {decision.reason} (t_c/t_d = {decision.ratio:.3f})")

# 4) run a batch of lookups on the accelerator path
queries = np.concatenate([keys[:64], rng.integers(10**6, 2 * 10**6, 64).astype(np.int32)])
ptr0, scr0 = it.init(jnp.asarray(queries), jnp.asarray(bucket_heads))
res = engine.execute(it, ptr0, scr0, max_iters=4096)
found = res.scratch[:, 2].astype(bool)
print(f"lookups: {found[:64].sum()}/64 hits on known keys, "
      f"{found[64:].sum()}/64 on absent keys, "
      f"mean chain hops {res.iters.mean():.1f}")

# 5) continuations: bound the per-request iteration budget and resume
ptr, scr, status, iters = execute_batched(it, arena, ptr0, scr0, max_iters=8)
n_maxed = int((status == STATUS_MAXED).sum())
print(f"with max_iters=8: {n_maxed} traversals suspended (scratch_pad returned)")
ptr, scr, status, iters = execute_batched(it, arena, ptr, scr, max_iters=4096)
assert int((np.asarray(status) == STATUS_DONE).sum()) == len(queries)
print("resumed to completion: all done -- continuation semantics OK")
