"""Chip smoke test: PulseService on a TPU at the paper's deployment shapes.

One pooled arena holds the paper's WebService hash table (200,000 keys in
4,096 buckets) and its WiredTiger B+tree (500,000 keys), both from
``repro.configs.pulse_paper`` (Table 3) at full key counts, built from
``--seed``.  The depths are this repo's, not Table 3's: a find walks about
half of a ~49-node chain (~26 iterations against the paper's 48), and the
fanout-8 B+tree is 7 levels deep (7 iterations against 25).  YCSB-style
traffic with Zipf 0.99 keys is served through
``PulseService(PulseEngine(arena), ...)``:

  read_xla      reads on both structures, default backend ("xla");
  update_mix    50/50 B+tree updates and finds (YCSB-A over the B+tree);
  readback      a find of every key the mix updated;
  read_kernel   the read traffic again with ``backend="kernel"``, so the
                compiled pulse_chase kernel serves it.

Every answer is checked against a plain Python dict built from the same seed
with the updates applied in submission order.  One tenant's requests are
admitted in order, writers take their structure group exclusively and two
writes to one key never share a write batch, so the service linearizes
requests in submission order.  Every update writes its own value (a
function of its request id and the seed), so a find after two updates of
one key shows which of them was applied last.

``--chips 4`` runs instead, and only, the sharded path: the same structures
range-partitioned over a 4-device ``mem`` mesh, serving the read mix and
the B+tree 50/50 mix through ``PulseService(PulseEngine(arena, mesh=...))``
(the default schedule, plus the write commit superstep).  Answers are
checked against the dict and against ``core.commit.sequential_commit_execute``
(reads, and the final arena after the mix's updates replayed in submission
order).

Earlier lines report the arena, build and compile times, per-phase request
counts and wall times, where each phase's quanta ran (``quanta_by_path``)
and the quanta sent to the host traversal (``cpu_node_quanta``, must be 0).
The last line is one JSON object naming the device.  Exits non-zero, with no
JSON line, when JAX sees no TPU or when any check fails.

Run:  python chip_smoke.py [--seed 0] [--chips 4]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

N_READS = 2048  # read requests per read phase
N_MIX = 1024  # requests in the 50/50 update/find phase
ZIPF_S = 0.99
MISS_RATE = 0.05  # reads of absent keys: exercises the not-found path
ABSENT_LO = 1 << 30  # present keys are drawn below this, absent ones above


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------- traffic ------------------------------------


def zipf_picker(rng, keys: np.ndarray, s: float = ZIPF_S):
    """Sampler of ``keys`` under Zipf(s) popularity (YCSB zipfian); the
    popularity ranks are scattered over the key space, as YCSB scrambles."""
    ranked = rng.permutation(keys)
    p = np.arange(1, len(keys) + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(p / p.sum())

    def pick(n: int) -> np.ndarray:
        idx = np.searchsorted(cdf, rng.random(n), side="right")
        return ranked[np.minimum(idx, len(keys) - 1)]

    return pick


def new_value(req_id: int, seed: int) -> int:
    """The value update request ``req_id`` writes (see module doc)."""
    return int((req_id * 2654435761 + seed * 40503 + 1) % (1 << 31))


@dataclasses.dataclass
class Data:
    seed: int
    hkeys: np.ndarray
    hvals: np.ndarray
    bkeys: np.ndarray
    bvals: np.ndarray
    n_buckets: int

    def reference(self) -> dict:
        return {
            "hash": dict(zip(self.hkeys.tolist(), self.hvals.tolist())),
            "btree": dict(zip(self.bkeys.tolist(), self.bvals.tolist())),
        }


def make_data(seed: int, ws, wt) -> Data:
    rng = np.random.default_rng(seed)
    hkeys = rng.choice(ABSENT_LO, ws.n_keys, replace=False).astype(np.int32)
    bkeys = rng.choice(ABSENT_LO, wt.n_keys, replace=False).astype(np.int32)
    hvals = rng.integers(0, 1 << 31, ws.n_keys, dtype=np.int64).astype(np.int32)
    bvals = rng.integers(0, 1 << 31, wt.n_keys, dtype=np.int64).astype(np.int32)
    return Data(seed, hkeys, hvals, bkeys, bvals, ws.n_buckets)


def read_requests(rng, data: Data, n: int, first_id: int):
    """n reads alternating hash / B+tree, Zipf keys, MISS_RATE absent keys."""
    from repro.serving.admission import TraversalRequest

    picks = {
        "hash": zipf_picker(rng, data.hkeys)(n),
        "btree": zipf_picker(rng, data.bkeys)(n),
    }
    out = []
    for i in range(n):
        s = ("hash", "btree")[i % 2]
        k = int(picks[s][i])
        if rng.random() < MISS_RATE:
            k = int(rng.integers(ABSENT_LO, (1 << 31) - 2))
        out.append(TraversalRequest(req_id=first_id + i, structure=s, query=k, tenant="ycsb"))
    return out


def mix_requests(rng, data: Data, n: int, first_id: int):
    """n B+tree requests, 50% updates and 50% finds, over Zipf keys."""
    from repro.serving.admission import TraversalRequest

    keys = zipf_picker(rng, data.bkeys)(n)
    out = []
    for i in range(n):
        k = int(keys[i])
        # the first two are an update and a find, so the warm-up compiles both
        if (i == 0) if i < 2 else rng.random() < 0.5:
            out.append(TraversalRequest(
                req_id=first_id + i, structure="btree_update", query=k,
                value=new_value(first_id + i, data.seed), tenant="ycsb",
            ))
        else:
            out.append(TraversalRequest(
                req_id=first_id + i, structure="btree", query=k, tenant="ycsb",
            ))
    return out


def expected(requests, ref: dict) -> list:
    """Walk the requests in submission order against the dict; updates
    mutate it.  Returns the expected (status, value, found) per request."""
    from repro.core.structures import btree

    out = []
    for r in requests:
        if r.structure == "btree_update":
            hit = r.query in ref["btree"]
            if hit:
                ref["btree"][r.query] = r.value
            out.append(("update", int(hit)))
        else:
            v = ref[r.structure].get(r.query)
            out.append((btree.KEY_NOT_FOUND if v is None else v, int(v is not None)))
    return out


def mismatches(requests, want) -> list[str]:
    from repro.core.iterator import STATUS_DONE
    from repro.core.structures import btree

    bad = []
    for r, w in zip(requests, want):
        res = r.result
        if r.status != STATUS_DONE or res is None:
            bad.append(f"req {r.req_id} {r.structure}({r.query}): status {r.status}")
        elif w[0] == "update":
            if int(res[btree.U_FOUND]) != w[1]:
                bad.append(f"req {r.req_id} update({r.query}): found {res[btree.U_FOUND]} want {w[1]}")
        elif (int(res[1]), int(res[2])) != w:
            bad.append(
                f"req {r.req_id} {r.structure}({r.query}): got value {int(res[1])} "
                f"found {int(res[2])}, want {w[0]} found {w[1]}"
            )
    return bad


# ------------------------------- system -------------------------------------


def build_arena(data: Data, num_shards: int):
    from repro.core.arena import ArenaBuilder
    from repro.core.structures import btree, hash_table

    rows = len(data.hkeys) + btree.node_estimate(len(data.bkeys))
    cap = -(-rows // (8 * num_shards)) * 8 * num_shards
    b = ArenaBuilder(cap, btree.NODE_WORDS, num_shards=num_shards)
    heads = hash_table.build_into(b, data.hkeys, data.hvals, data.n_buckets)
    root, height = btree.build_into(b, data.bkeys, data.bvals)
    return b.finish(), heads, root, height


def specs(heads, root, n_buckets: int, *, writes: bool):
    import jax.numpy as jnp

    from repro.core.structures import btree, hash_table
    from repro.serving.traversal_service import StructureSpec

    out = {
        "hash": StructureSpec(hash_table.find_iterator(n_buckets), (jnp.asarray(heads),)),
        "btree": StructureSpec(btree.find_iterator(), (root,), group="btree"),
    }
    if writes:
        out["btree_update"] = StructureSpec(
            btree.update_iterator(), (root,), group="btree", takes_value=True
        )
    return out


def serve(name: str, service, requests, ref: dict, *, warm: int = 8) -> dict:
    """Serve ``requests`` in order: the first ``warm`` (compiles included)
    and then the rest, timed apart.  Checks every answer; returns a record."""
    from repro.kernels.pulse_chase import ops as chase_ops

    want = expected(requests, ref)
    m = service.metrics
    before = dict(m.quanta_by_path), m.cpu_node_quanta
    t0 = time.perf_counter()
    service.run(requests[:warm])
    t1 = time.perf_counter()
    traces0 = chase_ops.CACHE_STATS.traces
    service.run(requests[warm:])
    t2 = time.perf_counter()
    paths = {k: v - before[0].get(k, 0) for k, v in m.quanta_by_path.items()}
    paths = {k: v for k, v in paths.items() if v}
    bad = mismatches(requests, want)
    iters = {}
    for s in sorted({r.structure for r in requests}):
        its = [r.iters for r in requests if r.structure == s]
        iters[s] = round(float(np.mean(its)), 2)
    rec = dict(
        phase=name, requests=len(requests), warm_requests=warm,
        warm_s=round(t1 - t0, 4), wall_s=round(t2 - t1, 4),
        quanta_by_path=paths, cpu_node_quanta=m.cpu_node_quanta - before[1],
        kernel_compiles_after_warm=chase_ops.CACHE_STATS.traces - traces0,
        mean_iters=iters, mismatches=len(bad),
    )
    log("phase " + json.dumps(rec))
    for line in bad[:10]:
        log(f"  mismatch: {line}")
    return rec


def shard_devices(arena) -> list[str]:
    out = []
    for sh in sorted(arena.data.addressable_shards, key=lambda s: s.index[0].start or 0):
        rows = sh.index[0]
        out.append(f"rows[{rows.start or 0}:{rows.stop or arena.capacity}]@{sh.device}")
    return out


def readback_requests(mix, first_id: int):
    """One find of every key the mix updated."""
    from repro.serving.admission import TraversalRequest

    updated = sorted({r.query for r in mix if r.structure == "btree_update"})
    return [
        TraversalRequest(req_id=first_id + i, structure="btree", query=k, tenant="ycsb")
        for i, k in enumerate(updated)
    ]


def in_order_layers(requests) -> list[list]:
    """Split the update requests, in submission order, into batches of
    distinct keys: batch j holds every key's j-th update.  Applying the
    batches in turn applies each key's updates in submission order; updates
    of different keys write different value words, so their relative order
    does not change the arena."""
    seen: dict[int, int] = {}
    layers: list[list] = []
    for r in requests:
        if r.structure != "btree_update":
            continue
        j = seen.get(r.query, 0)
        seen[r.query] = j + 1
        if j == len(layers):
            layers.append([])
        layers[j].append(r)
    return layers


def replay_updates(arena, root, requests):
    """The arena after ``requests``' B+tree updates, replayed in submission
    order on the sequential-commit oracle."""
    import jax.numpy as jnp

    from repro.core import commit
    from repro.core.structures import btree

    upd = btree.update_iterator()
    for layer in in_order_layers(requests):
        keys = jnp.asarray(np.array([r.query for r in layer], np.int32))
        vals = jnp.asarray(np.array([r.value for r in layer], np.int32))
        p0, s0 = upd.init(keys, vals, root)
        _, _, arena = commit.sequential_commit_execute(upd, arena, p0, s0)
    return arena


def run_one_chip(data: Data, *, n_reads: int = N_READS, n_mix: int = N_MIX) -> list[dict]:
    """The default smoke: XLA reads, the B+tree update mix, read-back, then
    kernel reads, all on one device."""
    from repro.core.engine import PulseEngine
    from repro.serving.admission import TraversalRequest
    from repro.serving.traversal_service import PulseService

    rng = np.random.default_rng(data.seed + 1)
    t0 = time.perf_counter()
    arena, heads, root, height = build_arena(data, 1)
    arena.data.block_until_ready()
    log(
        f"arena rows={arena.capacity} words={arena.node_words} "
        f"bytes={arena.data.nbytes} btree_height={height} "
        f"build_s={time.perf_counter() - t0:.4f} devices={shard_devices(arena)}"
    )
    ref = data.reference()
    engine = PulseEngine(arena)
    service = PulseService(engine, specs(heads, root, data.n_buckets, writes=True))
    reads = read_requests(rng, data, n_reads, 0)
    recs = [serve("read_xla", service, reads, ref)]
    mix = mix_requests(rng, data, n_mix, n_reads)
    recs.append(serve("update_mix", service, mix, ref))
    back = readback_requests(mix, n_reads + n_mix)
    recs.append(serve("readback", service, back, ref, warm=1))
    kservice = PulseService(
        engine, specs(heads, root, data.n_buckets, writes=False), backend="kernel"
    )
    kreads = [
        TraversalRequest(req_id=2 * n_reads + n_mix + len(back) + i,
                         structure=r.structure, query=r.query, tenant=r.tenant)
        for i, r in enumerate(reads)
    ]
    recs.append(serve("read_kernel", kservice, kreads, ref))
    return recs


def run_four_chips(data: Data, *, n_reads: int = N_READS, n_mix: int = N_MIX) -> list[dict]:
    """The sharded path: reads and the B+tree mix over a 4-shard mem mesh,
    checked against the dict and the sequential-commit oracle."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import commit, routing
    from repro.core.engine import PulseEngine
    from repro.core.structures import btree, hash_table
    from repro.distributed.sharding import mem_mesh
    from repro.serving.traversal_service import PulseService

    P_SHARDS = 4
    rng = np.random.default_rng(data.seed + 1)
    mesh = mem_mesh(P_SHARDS)
    t0 = time.perf_counter()
    arena, heads, root, height = build_arena(data, P_SHARDS)
    arena = dataclasses.replace(
        arena, data=jax.device_put(arena.data, NamedSharding(mesh, P("mem", None)))
    )
    arena.data.block_until_ready()
    arena0 = arena
    devs = shard_devices(arena)
    log(
        f"arena rows={arena.capacity} words={arena.node_words} "
        f"bytes={arena.data.nbytes} shards={P_SHARDS} btree_height={height} "
        f"build_s={time.perf_counter() - t0:.4f} shard_devices={devs}"
    )
    ref = data.reference()
    engine = PulseEngine(arena, mesh=mesh)
    service = PulseService(engine, specs(heads, root, data.n_buckets, writes=True))
    reads = read_requests(rng, data, n_reads, 0)
    recs = [serve("read_sharded", service, reads, ref)]
    mix = mix_requests(rng, data, n_mix, n_reads)
    recs.append(serve("update_mix_sharded", service, mix, ref))
    back = readback_requests(mix, n_reads + n_mix)
    recs.append(serve("readback_sharded", service, back, ref, warm=1))

    # the sequential-commit oracle: read answers on the initial arena, the
    # final arena after every update, read-back answers on that arena
    def oracle_reads(it, init_args, reqs, ar):
        q = jnp.asarray(np.array([r.query for r in reqs], np.int32))
        p0, s0 = it.init(q, *init_args)
        rec, _ = commit.sequential_commit_execute(it, ar, p0, s0)
        S = it.scratch_words
        got = np.asarray(rec)[:, routing.F_SCRATCH : routing.F_SCRATCH + S]
        return [
            f"req {r.req_id} {r.structure}({r.query}): oracle {g.tolist()} service {r.result.tolist()}"
            for r, g in zip(reqs, got)
            if not np.array_equal(g, r.result)
        ]

    hreads = [r for r in reads if r.structure == "hash"]
    breads = [r for r in reads if r.structure == "btree"]
    bad = oracle_reads(hash_table.find_iterator(data.n_buckets), (jnp.asarray(heads),), hreads, arena0)
    bad += oracle_reads(btree.find_iterator(), (root,), breads, arena0)
    oracle_arena = replay_updates(arena0, root, mix)
    arena_equal = bool(np.array_equal(np.asarray(oracle_arena.data), np.asarray(engine.arena.data)))
    if not arena_equal:
        bad.append("final arena differs from the sequential-commit oracle's")
    bad += oracle_reads(btree.find_iterator(), (root,), back, oracle_arena)
    final_devs = shard_devices(engine.arena)
    distinct = len({d.split("@")[1] for d in final_devs})
    if distinct != P_SHARDS:
        bad.append(f"arena shards on {distinct} distinct devices, want {P_SHARDS}")
    rec = dict(
        phase="oracle_sharded", reads_checked=len(reads) + len(back),
        updated_keys=len(back), final_arena_equal=arena_equal,
        shard_devices=final_devs, distinct_shard_devices=distinct,
        mismatches=len(bad),
    )
    log("phase " + json.dumps(rec))
    for line in bad[:10]:
        log(f"  mismatch: {line}")
    recs.append(rec)
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import use_compile_cache
    from repro.configs import pulse_paper

    cache = use_compile_cache()
    log(f"device platform={dev.platform} kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache}")
    t0 = time.perf_counter()
    data = make_data(args.seed, pulse_paper.WEBSERVICE, pulse_paper.WIREDTIGER)
    log(f"data seed={args.seed} webservice_keys={len(data.hkeys)} "
        f"buckets={data.n_buckets} wiredtiger_keys={len(data.bkeys)} "
        f"gen_s={time.perf_counter() - t0:.4f}")
    run = run_four_chips if args.chips == 4 else run_one_chip
    recs = run(data)
    failed = [r["phase"] for r in recs if r["mismatches"] or r.get("cpu_node_quanta")]
    if failed:
        print(f"chip_smoke: checks failed in {failed}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
