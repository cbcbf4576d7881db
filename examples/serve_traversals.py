"""Serve mixed pointer-traversal traffic through PulseService.

A minimal end-to-end tour of the serving layer (paper S4-S5 as a request
server):

  * four structure families live in ONE pooled arena (the disaggregated
    heap);
  * tenants submit find() traffic, one with tight deadlines -- and a writer
    tenant inserts fresh list keys through the write path (staged mutations
    + commit supersteps), barriered per structure group so its batch owns
    the "list" group exclusively while it runs;
  * PulseService admits requests into per-structure slot groups, runs each
    group a quantum of iterations per round, retires finished traversals
    (backfilling the slot), and resumes the rest as continuations.

Run:  PYTHONPATH=src python examples/serve_traversals.py
"""

import numpy as np
import jax.numpy as jnp

from repro.core.arena import ArenaBuilder
from repro.core.engine import PulseEngine
from repro.core.structures import btree, hash_table, linked_list, skiplist
from repro.serving.admission import TraversalRequest
from repro.serving.traversal_service import PulseService, StructureSpec
from repro.compile_cache import use_compile_cache

use_compile_cache()

RNG = np.random.default_rng(0)
N = 1024

# -- one pooled heap, four resident structures --------------------------------
b = ArenaBuilder(1 << 14, 20)
lkeys = np.arange(N, dtype=np.int32)
head = linked_list.build_into(b, lkeys, RNG.integers(0, 10**6, N).astype(np.int32))
bkeys = RNG.choice(np.arange(10**6, 2 * 10**6), N, replace=False).astype(np.int32)
root, _ = btree.build_into(b, bkeys, RNG.integers(0, 10**6, N).astype(np.int32))
hkeys = RNG.choice(np.arange(2 * 10**6, 3 * 10**6), N, replace=False).astype(np.int32)
heads = hash_table.build_into(b, hkeys, RNG.integers(0, 10**6, N).astype(np.int32), 128)
skeys = RNG.choice(np.arange(3 * 10**6, 4 * 10**6), N, replace=False).astype(np.int32)
shead = skiplist.build_into(b, skeys, RNG.integers(0, 10**6, N).astype(np.int32))
arena = b.finish()

# -- the service --------------------------------------------------------------
service = PulseService(
    PulseEngine(arena),
    {
        "list": StructureSpec(linked_list.find_iterator(), (head,), group="list"),
        "list_insert": StructureSpec(
            linked_list.insert_iterator(), (head,), group="list", takes_value=True
        ),
        "btree": StructureSpec(btree.find_iterator(), (root,)),
        "hash": StructureSpec(hash_table.find_iterator(128), (jnp.asarray(heads),)),
        "skip": StructureSpec(skiplist.find_iterator(), (shead,)),
    },
    slots_per_structure=32,
    quantum=16,
)

# -- traffic ------------------------------------------------------------------
keysets = {"list": lkeys, "btree": bkeys, "hash": hkeys, "skip": skeys}
names = list(keysets)
requests = []
for i in range(200):
    s = names[i % 4]
    requests.append(
        TraversalRequest(
            req_id=i,
            structure=s,
            query=int(keysets[s][RNG.integers(0, N)]),
            tenant=("latency-sensitive" if i % 5 == 0 else "batch"),
            deadline_ms=1000.0 if i % 5 == 0 else None,
        )
    )

# a writer tenant appends fresh keys, then reads them back in the same run
inserts = [
    TraversalRequest(
        req_id=1000 + j, structure="list_insert", query=10**7 + j,
        value=j * 11, tenant="writer",
    )
    for j in range(16)
]
readbacks = [
    TraversalRequest(
        req_id=2000 + j, structure="list", query=10**7 + j, tenant="writer"
    )
    for j in range(16)
]

metrics = service.run(requests + inserts + readbacks)
print(metrics.summary())
found = sum(
    int(r.result[2]) for r in requests if r.structure != "btree"
)
print(f"found flags set on {found} non-btree find requests")
print(
    f"write path: {metrics.writes_retired} inserts retired, "
    f"{metrics.commits} mutations committed"
)
ok = sum(int(r.result[1] == (r.req_id - 2000) * 11) for r in readbacks)
print(f"read-your-writes: {ok}/16 readbacks saw the inserted value")
for tenant, d in sorted(metrics.per_tenant.items()):
    lat = np.asarray(d["latencies_ms"])
    print(f"  {tenant}: {d['completed']} done, p50 {np.percentile(lat, 50):.1f} ms")
