"""Wall-clock benchmark: superstep schedules on an 8-shard mesh.

PR 1's active-set compaction cut *wire words*; the paper's headline claim
(Fig. 7-9) is wall-clock latency/throughput.  This harness measures exactly
that on an 8-shard mesh: the same compacted superstep schedule executed

  * **dispatched** -- one jitted superstep program per hop, the local-vs-
    fabric decision and the capacity ladder re-decided on the host between
    hops (PR 1 behavior);
  * **fused**      -- the whole traversal as a single device-resident
    ``lax.while_loop`` program (``core.routing`` ``schedule="fused"``): no
    host round-trip per hop, but each superstep still serializes local
    chase -> all_to_all -> wait;
  * **pipelined**  -- the fused loop's active set split into two wavefronts
    (``schedule="pipelined"``): the in-flight wavefront rides the fabric as
    carried loop state while the resident wavefront chases locally, and
    fabric-side coordination collapses to one stacked psum per superstep;
  * **ring**       -- the pipelined schedule on the ``lax.ppermute`` ring
    fabric (P-1 distance classes instead of one dense all_to_all).

All schedules are bit-identical to the single-node BSP oracle (asserted here
on every config); only the wall clock differs.  Reports per-superstep and
end-to-end latency for each config plus an end-to-end mixed-structure total.

Run:  PYTHONPATH=src python benchmarks/wallclock_bench.py
      PYTHONPATH=src python benchmarks/wallclock_bench.py --small --check \
          --json BENCH_wallclock.json
"""

from __future__ import annotations

import os

# must be set before jax initializes: the mesh needs a multi-device host
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import routing
from repro.distributed.sharding import mem_mesh
from repro.core.arena import ArenaBuilder
from repro.core.iterator import execute_batched
from repro.core.structures import btree, hash_table, linked_list, skiplist

P = 8
RNG = np.random.default_rng(42)
N_BUCKETS = 64


def _unique(n, lo, hi):
    return RNG.choice(np.arange(lo, hi, dtype=np.int64), n, replace=False).astype(
        np.int32
    )


def build_configs(small: bool):
    """Each config: (iterator, arena, ptr0, scratch0, max_iters).

    ``chain-skewed`` is the acceptance config: an interleaved linked list
    where half the batch retires in a few hops and half walks deep -- the
    schedule where per-hop host dispatch hurts most (hundreds of supersteps,
    each shipping almost nothing by the end).
    """
    n = 256 if small else 640
    B = 64 if small else 160
    cfgs = {}

    keys = np.arange(n, dtype=np.int32)
    vals = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, head = linked_list.build(keys, vals, num_shards=P, policy="interleaved")
    it = linked_list.find_iterator()
    q = np.concatenate(
        [RNG.integers(0, n // 16, B // 2), RNG.integers(n // 2, n, B // 2)]
    ).astype(np.int32)
    ptr0, scr0 = it.init(jnp.asarray(q), head)
    cfgs["chain-skewed"] = (it, ar, ptr0, scr0, 1 << 16)

    bkeys = _unique(n, 0, 10**6)
    ar, root, _ = btree.build(bkeys, vals, num_shards=P, policy="interleaved")
    it = btree.find_iterator()
    q = np.concatenate([bkeys[: B // 2], _unique(B // 2, 10**6, 2 * 10**6)])
    ptr0, scr0 = it.init(jnp.asarray(q), root)
    cfgs["btree-lookup"] = (it, ar, ptr0, scr0, 64)

    hkeys = _unique(n, 0, 10**6)
    ar, heads = hash_table.build(hkeys, vals, N_BUCKETS, num_shards=P, policy="interleaved")
    it = hash_table.find_iterator(N_BUCKETS)
    q = np.concatenate([hkeys[: B // 2], _unique(B // 2, 10**6, 2 * 10**6)])
    ptr0, scr0 = it.init(jnp.asarray(q), jnp.asarray(heads))
    cfgs["hash-probe"] = (it, ar, ptr0, scr0, 1 << 12)

    skeys = np.sort(_unique(n, 0, 10**6))
    ar, shead = skiplist.build(skeys, vals, num_shards=P, policy="interleaved")
    it = skiplist.find_iterator()
    q = np.concatenate([skeys[: B // 2], _unique(B // 2, 10**6, 2 * 10**6)])
    ptr0, scr0 = it.init(jnp.asarray(q), shead)
    cfgs["skiplist-search"] = (it, ar, ptr0, scr0, 1 << 12)

    return cfgs


MODES = {
    "dispatched": dict(schedule="dispatched"),
    "fused": dict(schedule="fused"),
    "pipelined": dict(schedule="pipelined"),
    "ring": dict(schedule="pipelined", fabric="ring"),
}


def bench_config(name, it, ar, ptr0, scr0, mesh, *, max_iters, repeats):
    o_ptr, o_scr, o_status, o_iters = execute_batched(
        it, ar, ptr0, scr0, max_iters=max_iters
    )
    B = int(np.asarray(ptr0).shape[0])
    out = {"batch": B}
    for mode, mode_kw in MODES.items():
        kw = dict(
            mesh=mesh, axis_name="mem", max_iters=max_iters, k_local=4,
            compact=True, **mode_kw,
        )
        rec, st = routing.distributed_execute(it, ar, ptr0, scr0, **kw)  # warmup
        np.testing.assert_array_equal(rec[:, routing.F_SCRATCH:], np.asarray(o_scr))
        np.testing.assert_array_equal(rec[:, routing.F_STATUS], np.asarray(o_status))
        np.testing.assert_array_equal(rec[:, routing.F_ITERS], np.asarray(o_iters))
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            rec, st = routing.distributed_execute(it, ar, ptr0, scr0, **kw)
            walls.append(time.perf_counter() - t0)
        p50 = float(np.percentile(walls, 50))
        out[mode] = {
            "wall_s_p50": p50,
            "wall_s_p99": float(np.percentile(walls, 99)),
            "per_superstep_ms": p50 / st.supersteps * 1e3,
            "supersteps": st.supersteps,
            "local_only_steps": st.local_only_steps,
            "wire_words": st.total_wire_words,
            "throughput_rps": B / p50,
        }
    # schedule-identity across modes (the bit-identity contract, stats side)
    ss = {m: out[m]["supersteps"] for m in MODES}
    ww = {m: out[m]["wire_words"] for m in MODES}
    assert len(set(ss.values())) == 1, f"superstep counts diverged: {ss}"
    assert len(set(ww.values())) == 1, f"wire accounting diverged: {ww}"
    out["speedup"] = out["dispatched"]["wall_s_p50"] / out["fused"]["wall_s_p50"]
    out["speedup_pipelined"] = (
        out["fused"]["wall_s_p50"] / out["pipelined"]["wall_s_p50"]
    )
    out["speedup_ring"] = out["fused"]["wall_s_p50"] / out["ring"]["wall_s_p50"]
    f, p = out["fused"], out["pipelined"]
    print(
        f"  {name:16s} steps={f['supersteps']:4d} "
        f"dispatched={out['dispatched']['wall_s_p50']*1e3:8.1f}ms "
        f"fused={f['wall_s_p50']*1e3:8.1f}ms "
        f"pipelined={p['wall_s_p50']*1e3:8.1f}ms "
        f"ring={out['ring']['wall_s_p50']*1e3:8.1f}ms "
        f"fused/disp={out['speedup']:.2f}x pipe/fused={out['speedup_pipelined']:.2f}x"
    )
    return out


def bench_rw_mixed(mesh, *, small: bool, repeats: int):
    """Mixed 50/50 read-write series: finds racing tail-inserts in one batch
    on an interleaved chain (the write path's commit supersteps on every
    schedule).  Asserts schedule identity -- supersteps, wire words, commit
    counts, AND the final arena contents (data + heap registers) must be
    bit-identical across dispatched/fused/pipelined x dense/ring."""
    n = 128 if small else 256
    B = 32 if small else 64
    b = ArenaBuilder(4 * n, 4, num_shards=P, policy="interleaved")
    keys = np.arange(10, 10 + n, dtype=np.int32)
    head = linked_list.build_into(b, keys, keys * 3)
    ar = b.finish()
    it = linked_list.rw_iterator()
    ops = np.tile([1, 0], B // 2).astype(np.int32)  # 50% insert / 50% find
    qk = np.empty(B, np.int32)
    qk[ops == 1] = np.arange(B // 2) + 10_000
    qk[ops == 0] = keys[RNG.permutation(n)[: B // 2]]
    qv = (np.arange(B) + 5).astype(np.int32)
    ptr0, scr0 = it.init(ops, qk, qv, head)

    out = {"batch": B, "writes": int((ops == 1).sum())}
    arenas = {}
    for mode, mode_kw in MODES.items():
        kw = dict(
            mesh=mesh, axis_name="mem", max_iters=1 << 14, k_local=4,
            compact=True, **mode_kw,
        )
        rec, st, ar_out = routing.distributed_execute(it, ar, ptr0, scr0, **kw)
        arenas[mode] = (np.asarray(ar_out.data), np.asarray(ar_out.heap))
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            rec, st, ar_out = routing.distributed_execute(it, ar, ptr0, scr0, **kw)
            walls.append(time.perf_counter() - t0)
        p50 = float(np.percentile(walls, 50))
        out[mode] = {
            "wall_s_p50": p50,
            "supersteps": st.supersteps,
            "wire_words": st.total_wire_words,
            "commits": st.commits,
            "epochs": st.epochs,
            "throughput_rps": B / p50,
        }
    # schedule identity: stats AND the post-commit heap must agree bit-for-bit
    for field in ("supersteps", "wire_words", "commits"):
        vals = {m: out[m][field] for m in MODES}
        assert len(set(vals.values())) == 1, f"rw {field} diverged: {vals}"
    base_data, base_heap = arenas["dispatched"]
    for mode, (d, h) in arenas.items():
        np.testing.assert_array_equal(d, base_data, err_msg=f"rw arena: {mode}")
        np.testing.assert_array_equal(h, base_heap, err_msg=f"rw heap: {mode}")
    out["speedup_pipelined"] = (
        out["fused"]["wall_s_p50"] / out["pipelined"]["wall_s_p50"]
    )
    f = out["fused"]
    print(
        f"  {'rw-mixed 50/50':16s} steps={f['supersteps']:4d} "
        f"commits={f['commits']} "
        f"dispatched={out['dispatched']['wall_s_p50']*1e3:8.1f}ms "
        f"fused={f['wall_s_p50']*1e3:8.1f}ms "
        f"pipelined={out['pipelined']['wall_s_p50']*1e3:8.1f}ms "
        f"(arena + stats bit-identical across schedules)"
    )
    return out


def bench_verify_specialization(mesh, *, small: bool, repeats: int):
    """Read-only wire-word reduction from pulse-verify certificates.

    Same traversal twice: the verified ``list_find`` ISA program (read-only
    certificate => mutation record lanes skipped, per-hop access probe
    elided) against a dead-store variant admitted with ``verify=False`` --
    the conservative opcode scan routes it down the write path, arming the
    mutation lanes on every fabric crossing even though the store is
    unreachable.  Results are bit-identical; the wire-word gap is what the
    certificate buys."""
    from repro.core import isa
    from repro.core.structures import isa_programs

    n = 128 if small else 320
    B = 32 if small else 64
    keys = np.arange(n, dtype=np.int32)
    vals = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, head = linked_list.build(keys, vals, num_shards=P, policy="interleaved")
    q = np.concatenate(
        [keys[RNG.permutation(n)[: B // 2]], RNG.integers(n, 2 * n, B // 2)]
    ).astype(np.int32)
    ptr0, scr0 = linked_list.find_iterator().init(jnp.asarray(q), head)

    prog = isa_programs.list_find_program()
    vm_ro = isa.as_pulse_iterator(prog)  # carries the read-only certificate
    dead = isa.Program(
        np.vstack([prog.code, [[isa.STOREN, 2, 0, 1]]]),
        prog.scratch_words, prog.node_words, name="list_find_dead_store",
    )
    vm_rw = isa.as_pulse_iterator(dead, verify=False)  # opcode-scan fallback
    assert routing.can_elide_access_check(vm_ro, ar)

    S = vm_ro.scratch_words
    payload_cols = [routing.F_ID, routing.F_PTR, routing.F_STATUS, routing.F_ITERS]

    def payload(rec):
        rec = np.asarray(rec)
        return np.concatenate(
            [rec[:, payload_cols], rec[:, routing.F_SCRATCH: routing.F_SCRATCH + S]],
            axis=1,
        )

    out = {"batch": B}
    recs = {}
    for label, vm in (("verified_ro", vm_ro), ("unverified_rw", vm_rw)):
        kw = dict(
            mesh=mesh, axis_name="mem", max_iters=1 << 14, k_local=4,
            compact=True, schedule="fused",
        )
        res = routing.distributed_execute(vm, ar, ptr0, scr0, **kw)  # warmup
        rec, st = res[0], res[1]
        recs[label] = payload(rec)
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = routing.distributed_execute(vm, ar, ptr0, scr0, **kw)
            walls.append(time.perf_counter() - t0)
        out[label] = {
            "wall_s_p50": float(np.percentile(walls, 50)),
            "supersteps": st.supersteps,
            "wire_words": st.total_wire_words,
        }
    np.testing.assert_array_equal(recs["verified_ro"], recs["unverified_rw"])
    out["wire_reduction"] = 1 - (
        out["verified_ro"]["wire_words"] / out["unverified_rw"]["wire_words"]
    )
    print(
        f"  {'verify-readonly':16s} steps={out['verified_ro']['supersteps']:4d} "
        f"wire={out['verified_ro']['wire_words']} vs "
        f"{out['unverified_rw']['wire_words']} unverified "
        f"(-{out['wire_reduction']:.0%}, results bit-identical)"
    )
    return out


def main(argv=None):
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--json",
        nargs="?",
        const="BENCH_wallclock.json",
        default=None,
        metavar="PATH",
        help="write machine-readable results (default path: BENCH_wallclock.json)",
    )
    ap.add_argument("--small", action="store_true", help="CI smoke sizes")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument(
        "--check",
        action="store_true",
        help="fail unless fused beats per-hop dispatch (>=1.3x on chain-skewed, "
        ">=1x end-to-end) -- the CI perf gate",
    )
    args = ap.parse_args(argv)

    mesh = mem_mesh(P)
    assert jax.device_count() >= P, jax.devices()
    cfgs = build_configs(args.small)
    print(
        f"superstep schedules (dispatched/fused/pipelined/ring), {P} shards, "
        f"repeats={args.repeats}"
    )
    results = {}
    for name, (it, ar, ptr0, scr0, max_iters) in cfgs.items():
        results[name] = bench_config(
            name, it, ar, ptr0, scr0, mesh, max_iters=max_iters, repeats=args.repeats
        )

    # read-only configs drive the e2e aggregate; the rw series reports (and
    # asserts schedule identity) separately -- its commit phases serialize by
    # design, a different regime than the read-path overlap being gated
    e2e = {
        mode: sum(r[mode]["wall_s_p50"] for r in results.values())
        for mode in MODES
    }
    results["rw-mixed"] = bench_rw_mixed(mesh, small=args.small, repeats=args.repeats)
    results["verify-readonly"] = bench_verify_specialization(
        mesh, small=args.small, repeats=args.repeats
    )
    e2e["speedup"] = e2e["dispatched"] / e2e["fused"]
    e2e["speedup_pipelined"] = e2e["fused"] / e2e["pipelined"]
    e2e["speedup_ring"] = e2e["fused"] / e2e["ring"]
    print(
        f"  end-to-end mixed: dispatched={e2e['dispatched']*1e3:.1f}ms "
        f"fused={e2e['fused']*1e3:.1f}ms pipelined={e2e['pipelined']*1e3:.1f}ms "
        f"ring={e2e['ring']*1e3:.1f}ms "
        f"fused/disp={e2e['speedup']:.2f}x pipe/fused={e2e['speedup_pipelined']:.2f}x"
    )

    if args.json:
        payload = {
            "benchmark": "wallclock_bench",
            "config": {
                "shards": P,
                "small": bool(args.small),
                "repeats": args.repeats,
            },
            "results": results,
            "end_to_end": e2e,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")

    if args.check:
        chain = results["chain-skewed"]["speedup"]
        assert chain >= 1.3, (
            f"fused routing must beat per-hop dispatch by >=1.3x on the "
            f"skewed-depth chain, got {chain:.2f}x"
        )
        assert e2e["speedup"] >= 1.0, (
            f"fused routing slower than per-hop dispatch end-to-end: "
            f"{e2e['speedup']:.2f}x"
        )
        # the wavefront-pipelined gate: 1.2x on CI smoke sizes (collectives
        # are cheap relative to dispatch at tiny pools), 1.5x -- the
        # acceptance target -- at full size where hundreds of supersteps
        # amortize the compile
        need = 1.2 if args.small else 1.5
        pipe = results["chain-skewed"]["speedup_pipelined"]
        assert pipe >= need, (
            f"pipelined schedule must beat fused-serialized by >={need}x on "
            f"the skewed-depth chain, got {pipe:.2f}x"
        )
        assert e2e["speedup_pipelined"] >= 1.0, (
            f"pipelined schedule slower than fused end-to-end: "
            f"{e2e['speedup_pipelined']:.2f}x"
        )
        rw = results["rw-mixed"]
        assert rw["dispatched"]["commits"] > 0, "rw series committed nothing"
        vr = results["verify-readonly"]["wire_reduction"]
        assert vr >= 0.2, (
            f"read-only certificate must skip the mutation record lanes "
            f"(expected >=20% wire-word reduction, got {vr:.0%})"
        )
        print(
            f"  perf gate ok: chain-skewed fused/disp {chain:.2f}x (>=1.3), "
            f"pipelined/fused {pipe:.2f}x (>={need}), end-to-end "
            f"{e2e['speedup']:.2f}x / {e2e['speedup_pipelined']:.2f}x (>=1.0); "
            f"rw-mixed identity ok ({rw['dispatched']['commits']} commits, "
            f"stats + final arena bit-identical across schedules); "
            f"verify-readonly wire -{vr:.0%} (>=20%)"
        )


if __name__ == "__main__":
    main()
