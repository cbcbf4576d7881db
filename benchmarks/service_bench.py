"""Serving-layer benchmark: compacted supersteps + PulseService throughput
and the Fig. 7 tail-latency reproduction (sync vs async pipeline).

Four experiments:

  1. **Compacted routing** -- a skewed distributed workload (half the batch
     finishes early, the rest keep walking) on an 8-way mesh.  Reports the
     per-superstep wire payload (int32 words shipped through the all_to_all)
     for the bulk-synchronous baseline vs compacted execution, and checks the
     paper-style claim: once half the batch has finished, the compacted
     fabric carries >= 30% fewer record-words per superstep.

  2. **PulseService async vs sync** -- the same open-loop Poisson arrival
     trace (seeded; the seed is recorded in the JSON) served twice at an
     offered rate above saturation: once by the legacy synchronous loop,
     once by the async device-runner pipeline with SLO-aware quantum
     sizing.  Under ``--check`` this gates async throughput >= 1.3x sync
     with p99 <= 1.1x at the matched load, then sweeps an offered-RPS
     ladder (multiples of the measured sync service rate) recording
     p50/p99/p999 per rung -- the Fig. 7 curves -- and gates the async
     saturation point at >= 2x the sync service rate.  A final overload
     rung exercises per-tenant rate limiting + bounded-queue shedding.

  3. **PulseService mixed workload** -- a mixed 4-structure workload (list
     walk, B-tree lookup, hash-chain probe, skiplist search) from 3
     tenants served end-to-end through continuous batching; reports
     p50/p99 latency, throughput, utilization, and per-tenant counts.

  4. **LM batched prefill** -- the ContinuousBatcher's admission path:
     batched full-sequence prefill (one jitted call per admission) vs the
     legacy token-by-token slot prefill, on a reduced LM config.  Checks
     outputs are identical and reports the prefill-call reduction + wall
     clock for both.

Run:  PYTHONPATH=src python benchmarks/service_bench.py
      PYTHONPATH=src python benchmarks/service_bench.py --small --json BENCH_service.json
      PYTHONPATH=src python benchmarks/service_bench.py --arrival poisson:500 --seed 7
      PYTHONPATH=src python benchmarks/service_bench.py --small --arrival poisson:300 --check
      # open-loop Poisson arrivals (offered rate in req/s) instead of the
      # closed-loop logical rounds; arrival generation is seeded by --seed
      # and the realized process is emitted into the JSON (Fig. 7 runs)
"""

from __future__ import annotations

import os

# must be set before jax initializes: experiment 1 needs a multi-device host
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
from repro.core.engine import PulseEngine
from repro.core.structures import btree, hash_table, linked_list, skiplist
from repro.serving.admission import TraversalRequest
from repro.serving.traversal_service import PulseService, StructureSpec

RNG = np.random.default_rng(42)
P = 8


def bench_compacted_routing(n=2048, B=512, k_local=4):
    """Skewed list-walk workload: half shallow (retire fast), half deep."""
    keys = np.arange(n, dtype=np.int32)
    values = RNG.integers(0, 10**6, n).astype(np.int32)
    ar, head = linked_list.build(keys, values, num_shards=P, policy="interleaved")
    it = linked_list.find_iterator()
    q = np.concatenate(
        [
            RNG.integers(0, n // 16, B // 2),  # shallow: finish early
            RNG.integers(n // 2, n, B // 2),  # deep: keep walking
        ]
    ).astype(np.int32)
    ptr0, scr0 = it.init(jnp.asarray(q), head)
    mesh = mem_mesh(P)

    runs = {}
    for compact in (False, True):
        t0 = time.perf_counter()
        rec, st = routing.distributed_execute(
            it, ar, ptr0, scr0, mesh=mesh, axis_name="mem",
            max_iters=1 << 20, k_local=k_local, compact=compact,
        )
        dt = time.perf_counter() - t0
        runs[compact] = (rec, st, dt)
        print(
            f"  {'compacted' if compact else 'baseline '}: "
            f"supersteps={st.supersteps} wire_words={st.total_wire_words:,} "
            f"local_only={st.local_only_steps} wall={dt:.1f}s"
        )

    (rec_b, st_b, _), (rec_c, st_c, _) = runs[False], runs[True]
    np.testing.assert_array_equal(
        rec_b[:, routing.F_SCRATCH:], rec_c[:, routing.F_SCRATCH:]
    )
    np.testing.assert_array_equal(rec_b[:, routing.F_STATUS], rec_c[:, routing.F_STATUS])
    print("  results identical (compaction is schedule-only)")

    # the acceptance claim: compare per-superstep wire once half the batch
    # finished.  Baseline wire is constant, so its half-done wire == any step.
    half = B // 2
    base_wire = st_b.wire_words_per_step[0]
    idx = next(i for i, a in enumerate(st_c.active_per_step) if a <= half)
    # average compacted payload over the post-half-done tail (routed + skipped)
    tail = st_c.wire_words_per_step[idx:]
    tail_mean = float(np.mean(tail))
    reduction = 1.0 - tail_mean / base_wire
    print(
        f"  per-superstep wire once half finished: baseline={base_wire:,} "
        f"compacted(mean)={tail_mean:,.0f} reduction={reduction:.0%}"
    )
    assert reduction >= 0.30, (
        f"compacted routing must cut the half-done per-superstep payload by "
        f">=30%, got {reduction:.0%}"
    )
    total_red = 1.0 - st_c.total_wire_words / st_b.total_wire_words
    print(f"  total wire reduction: {total_red:.0%}")
    return {
        "baseline_wire_words": st_b.total_wire_words,
        "compacted_wire_words": st_c.total_wire_words,
        "half_done_reduction": reduction,
        "total_reduction": total_red,
    }


def build_mixed_heap(n_per=2048):
    """One pooled arena hosting all four structure families (paper S2: the
    memory pool is shared; the switch routes by address range)."""
    b = ArenaBuilder(1 << 16, 20)
    lkeys = np.arange(n_per, dtype=np.int32)
    lvals = RNG.integers(0, 10**6, n_per).astype(np.int32)
    head = linked_list.build_into(b, lkeys, lvals)
    bkeys = RNG.choice(np.arange(10**6, 2 * 10**6), n_per, replace=False).astype(np.int32)
    bvals = RNG.integers(0, 10**6, n_per).astype(np.int32)
    root, _ = btree.build_into(b, bkeys, bvals)
    hkeys = RNG.choice(np.arange(2 * 10**6, 3 * 10**6), n_per, replace=False).astype(np.int32)
    hvals = RNG.integers(0, 10**6, n_per).astype(np.int32)
    heads = hash_table.build_into(b, hkeys, hvals, 256)
    skeys = RNG.choice(np.arange(3 * 10**6, 4 * 10**6), n_per, replace=False).astype(np.int32)
    svals = RNG.integers(0, 10**6, n_per).astype(np.int32)
    shead = skiplist.build_into(b, skeys, svals)
    arena = b.finish()
    structures = {
        "list": StructureSpec(linked_list.find_iterator(), (head,)),
        "btree": StructureSpec(btree.find_iterator(), (root,)),
        "hash": StructureSpec(hash_table.find_iterator(256), (jnp.asarray(heads),)),
        "skip": StructureSpec(skiplist.find_iterator(), (shead,)),
    }
    keysets = {"list": lkeys, "btree": bkeys, "hash": hkeys, "skip": skeys}
    return arena, structures, keysets


def parse_arrival(spec: str | None):
    """``--arrival=poisson:<rps>`` -> ("poisson", rps); None -> closed loop."""
    if spec is None:
        return None
    kind, _, rate = spec.partition(":")
    if kind != "poisson" or not rate:
        raise ValueError(f"unknown arrival spec {spec!r} (want poisson:<rps>)")
    rps = float(rate)
    if rps <= 0:
        raise ValueError("poisson rate must be > 0")
    return ("poisson", rps)


def _make_request_specs(keysets, n, rng, deadline_ms=2000.0):
    """Immutable request blueprints -- materialized fresh per serving run so
    sync and async modes see byte-identical workloads."""
    names = list(keysets)
    tenants = ["tenant-a", "tenant-b", "tenant-c"]
    specs = []
    for i in range(n):
        s = names[rng.integers(0, len(names))]
        ks = keysets[s]
        # 10% misses exercise the not-found path
        key = (
            int(ks[rng.integers(0, len(ks))])
            if rng.random() > 0.1
            else int(rng.integers(5 * 10**6, 6 * 10**6))
        )
        specs.append((s, key, tenants[i % len(tenants)], deadline_ms))
    return specs


def _materialize(specs):
    return [
        TraversalRequest(req_id=i, structure=s, query=k, tenant=t, deadline_ms=d)
        for i, (s, k, t, d) in enumerate(specs)
    ]


def drive_open_loop(svc, reqs, t_arr):
    """Open-loop driver: exponential inter-arrivals in *wall-clock* time,
    submitted when due regardless of service backlog (the Fig. 7
    tail-latency regime: the arrival process never waits for the server)."""
    t0 = time.perf_counter()
    nxt, n = 0, len(reqs)
    while nxt < n or svc._busy():
        now = time.perf_counter() - t0
        while nxt < n and t_arr[nxt] <= now:
            svc.submit(reqs[nxt])
            nxt += 1
        if nxt < n and not svc._busy():
            # idle server, next arrival in the future: wait for it
            time.sleep(max(0.0, t_arr[nxt] - (time.perf_counter() - t0)))
            continue
        svc.step()
    svc.close()
    svc._drain_emit()
    m = svc.metrics
    m.wall_s += time.perf_counter() - t0
    return m


def _run_mode(
    engine,
    structures,
    specs,
    t_arr,
    *,
    mode,
    slots,
    quantum,
    max_quantum,
    max_pending=None,
    rate_limit_rps=None,
    rate_limit_burst=None,
):
    """One serving run over a fixed arrival trace.  The engine is shared
    across runs (read-only workload), so its compiled executables stay warm
    and every run measures steady-state serving."""
    kw = {}
    if mode == "async":
        kw.update(
            pipeline="async",
            min_quantum=max(1, quantum // 2),
            max_quantum=max_quantum,
        )
    if max_pending is not None:
        kw["max_pending"] = max_pending
    if rate_limit_rps is not None:
        kw["rate_limit_rps"] = rate_limit_rps
        kw["rate_limit_burst"] = rate_limit_burst
    svc = PulseService(
        engine, structures, slots_per_structure=slots, quantum=quantum, **kw
    )
    reqs = _materialize(specs)
    return drive_open_loop(svc, reqs, t_arr), reqs


def _point(m, offered):
    return {
        "offered_rps": float(offered),
        "throughput_rps": float(m.throughput_rps),
        "p50_ms": float(m.p50_ms),
        "p99_ms": float(m.p99_ms),
        "p999_ms": float(m.p999_ms),
        "completed": int(m.completed),
        "shed": int(m.shed),
        "rounds": int(m.rounds),
        "deadline_hit_rate": float(m.deadline_hit_rate),
        "quantum_range": [int(m.quantum_min_used), int(m.quantum_max_used)],
    }


def bench_async_pipeline(
    offered_rps, n_requests=240, slots=32, quantum=8, max_quantum=256,
    seed=42, check=False, sweep_requests=None,
):
    """Async device-runner pipeline vs the synchronous loop, then the Fig. 7
    offered-RPS ladder.  All arrival traces derive from ``seed``."""
    arena, structures, keysets = build_mixed_heap()
    engine = PulseEngine(arena)
    arr = np.random.default_rng(seed)
    specs = _make_request_specs(keysets, n_requests, arr)
    t_arr = np.cumsum(arr.exponential(1.0 / offered_rps, n_requests))
    out = {
        "seed": int(seed),
        "offered_rps": float(offered_rps),
        "n_requests": int(n_requests),
        "quantum": int(quantum),
        "max_quantum": int(max_quantum),
    }
    # warm the per-structure compiles once; every run below reuses them
    warm_svc = PulseService(
        engine, structures, slots_per_structure=slots, quantum=quantum
    )
    warm_svc.run(
        [
            TraversalRequest(10**6 + j, s, int(keysets[s][0]))
            for j, s in enumerate(structures)
        ]
    )

    # --- matched-load comparison (offered above saturation for both) -------
    res = {}
    for mode in ("sync", "async"):
        m, _ = _run_mode(
            engine, structures, specs, t_arr,
            mode=mode, slots=slots, quantum=quantum, max_quantum=max_quantum,
        )
        assert m.completed == n_requests, (mode, m.completed)
        res[mode] = m
        out[mode] = _point(m, offered_rps)
        print(
            f"  {mode:5s}: throughput={m.throughput_rps:6.0f} rps "
            f"p50={m.p50_ms:7.1f}ms p99={m.p99_ms:7.1f}ms "
            f"p999={m.p999_ms:7.1f}ms rounds={m.rounds} "
            f"quantum=[{m.quantum_min_used},{m.quantum_max_used}]"
        )
    speedup = res["async"].throughput_rps / res["sync"].throughput_rps
    p99_ratio = res["async"].p99_ms / res["sync"].p99_ms
    out["throughput_speedup"] = float(speedup)
    out["p99_ratio"] = float(p99_ratio)
    print(
        f"  async/sync at matched {offered_rps:.0f} rps: "
        f"throughput {speedup:.2f}x, p99 {p99_ratio:.2f}x"
    )
    if check:
        assert speedup >= 1.3, (
            f"async pipeline must serve >=1.3x sync throughput, got {speedup:.2f}x"
        )
        assert p99_ratio <= 1.1, (
            f"async p99 must stay within 1.1x of sync, got {p99_ratio:.2f}x"
        )

    # --- Fig. 7 ladder: p50/p99/p999 vs offered RPS ------------------------
    # rungs are multiples of the measured sync service rate, so the sweep is
    # machine-speed-invariant; sync's saturation throughput IS its service
    # rate (open-loop overload), and the async gate is "sustain 2x that".
    sync_rate = res["sync"].throughput_rps
    n_sweep = sweep_requests or max(60, n_requests // 2)
    # one workload spec set for every rung -- only the arrival rate varies,
    # so the rungs trace a load-latency curve, not workload noise
    sweep_rng = np.random.default_rng([seed, 1])
    sweep_specs = _make_request_specs(keysets, n_sweep, sweep_rng)
    rungs = []
    for ri, mult in enumerate((0.5, 1.0, 2.0, 3.0)):
        rate = mult * sync_rate
        rung_t = np.cumsum(
            np.random.default_rng([seed, 2, ri]).exponential(1.0 / rate, n_sweep)
        )
        modes = ("sync", "async") if mult <= 1.0 else ("async",)
        for mode in modes:
            m, _ = _run_mode(
                engine, structures, sweep_specs, rung_t,
                mode=mode, slots=slots, quantum=quantum,
                max_quantum=max_quantum,
            )
            pt = _point(m, rate)
            pt.update(mode=mode, multiple_of_sync_rate=mult)
            pt["sustained"] = bool(m.throughput_rps >= 0.8 * rate)
            rungs.append(pt)
            print(
                f"  fig7 {mode:5s} @ {mult:3.1f}x sync ({rate:5.0f} rps): "
                f"tput={m.throughput_rps:5.0f} p50={m.p50_ms:7.1f}ms "
                f"p99={m.p99_ms:7.1f}ms p999={m.p999_ms:7.1f}ms "
                f"{'sustained' if pt['sustained'] else 'SATURATED'}"
            )
    out["fig7"] = rungs
    async_sat = max(
        (r["offered_rps"] for r in rungs if r["mode"] == "async" and r["sustained"]),
        default=0.0,
    )
    out["sync_saturation_rps"] = float(sync_rate)
    out["async_saturation_rps"] = float(async_sat)
    print(
        f"  saturation: sync={sync_rate:.0f} rps async>={async_sat:.0f} rps "
        f"({async_sat / sync_rate:.1f}x)"
    )
    if check:
        assert async_sat >= 2.0 * sync_rate, (
            f"async must sustain >=2x sync saturation "
            f"({async_sat:.0f} vs {sync_rate:.0f} rps)"
        )

    # --- overload rung: rate limiting + bounded-queue shedding -------------
    over_rate = 6.0 * sync_rate
    over_t = np.cumsum(
        np.random.default_rng([seed, 99]).exponential(1.0 / over_rate, n_sweep)
    )
    max_pending = 2 * slots
    # per-tenant bucket well under each tenant's offered share (over_rate/3),
    # with a small burst so the bucket actually empties within the run
    m, reqs = _run_mode(
        engine, structures, sweep_specs, over_t,
        mode="async", slots=slots, quantum=quantum, max_quantum=max_quantum,
        max_pending=max_pending,
        rate_limit_rps=max(1.0, sync_rate / 2), rate_limit_burst=4,
    )
    assert m.completed + m.shed == n_sweep, (m.completed, m.shed)
    assert m.queue_depth_max <= max_pending, m.queue_depth_max
    out["overload"] = {
        **_point(m, over_rate),
        "max_pending": max_pending,
        "queue_depth_max": int(m.queue_depth_max),
        "shed_frac": float(m.shed / n_sweep),
    }
    print(
        f"  overload @ {over_rate:.0f} rps: completed={m.completed} "
        f"shed={m.shed} ({m.shed / n_sweep:.0%}) "
        f"queue_max={m.queue_depth_max}/{max_pending} "
        f"p99={m.p99_ms:.1f}ms deadline_hit={m.deadline_hit_rate:.0%}"
    )
    if check:
        assert m.shed > 0, "overload rung must shed load"
    return out


def bench_service(n_requests=600, slots=64, quantum=16, arrival=None, seed=42):
    arena, structures, keysets = build_mixed_heap()
    engine = PulseEngine(arena)
    svc = PulseService(
        engine, structures, slots_per_structure=slots, quantum=quantum
    )

    names = list(structures)
    tenants = ["tenant-a", "tenant-b", "tenant-c"]
    reqs = []
    for i in range(n_requests):
        s = names[RNG.integers(0, len(names))]
        ks = keysets[s]
        # 10% misses exercise the not-found path
        key = (
            int(ks[RNG.integers(0, len(ks))])
            if RNG.random() > 0.1
            else int(RNG.integers(5 * 10**6, 6 * 10**6))
        )
        reqs.append(
            TraversalRequest(
                req_id=i,
                structure=s,
                query=key,
                tenant=tenants[i % len(tenants)],
                deadline_ms=2000.0 if i % 3 == 0 else None,
                arrive_round=i // (2 * slots),  # closed-loop trickle default
            )
        )

    # warm the per-group compile so latency numbers reflect steady state
    warm = [
        TraversalRequest(10**6 + j, s, int(keysets[s][0]))
        for j, s in enumerate(names)
    ]
    svc.run(warm)
    svc.metrics = type(svc.metrics)()  # reset accounting after warmup

    arrival_info = {"process": "closed-loop", "rounds_per_wave": 1}
    if arrival is None:
        m = svc.run(reqs)
    else:
        # open-loop Poisson: exponential inter-arrivals in *wall-clock* time,
        # submitted when due regardless of service backlog (the Fig. 7
        # tail-latency regime: the arrival process never waits for the server)
        # arrival generation is seeded independently of the workload RNG so
        # overload runs replay bit-identically under the same --seed
        _, rps = arrival
        arr_rng = np.random.default_rng(seed)
        gaps = arr_rng.exponential(1.0 / rps, n_requests)
        t_arr = np.cumsum(gaps)
        for r in reqs:
            r.arrive_round = 0
        m = drive_open_loop(svc, reqs, t_arr)
        arrival_info = {
            "process": "poisson",
            "seed": int(seed),
            "offered_rps": rps,
            "achieved_arrival_rps": float(n_requests / t_arr[-1]),
            "interarrival_mean_ms": float(np.mean(gaps) * 1e3),
            "interarrival_p99_ms": float(np.percentile(gaps, 99) * 1e3),
            "arrival_span_s": float(t_arr[-1]),
        }
    print(f"  {m.summary()}")
    if arrival is not None:
        print(
            f"  open-loop poisson: offered={arrival_info['offered_rps']:.0f} rps "
            f"achieved={arrival_info['achieved_arrival_rps']:.0f} rps "
            f"span={arrival_info['arrival_span_s']:.2f}s"
        )
    for t, d in sorted(m.per_tenant.items()):
        lat = np.asarray(d["latencies_ms"])
        print(
            f"    {t}: completed={d['completed']} "
            f"p50={np.percentile(lat, 50):.1f}ms p99={np.percentile(lat, 99):.1f}ms"
        )
    if m.deadlines_met + m.deadlines_missed:
        print(f"    deadline hit rate: {m.deadline_hit_rate:.0%}")
    assert m.completed == n_requests
    # NOTE: no wire_words here -- this experiment serves through a single-node
    # engine (no mesh), so the distributed wire accounting is structurally 0;
    # the JSON's wire trajectory comes from the compacted-routing experiment.
    return {
        "completed": m.completed,
        "p50_ms": m.p50_ms,
        "p99_ms": m.p99_ms,
        "throughput_rps": m.throughput_rps,
        "utilization": m.utilization,
        "arrival": arrival_info,
    }


def bench_batched_prefill(n_requests=12, prompt_len=8, max_new=6):
    """Admission throughput: batched prefill vs token-by-token slot prefill.

    The legacy path runs one full-batch decode_step per prompt token per
    admitted request; the batched path absorbs a whole admission's prompts
    in one jitted prefill call per distinct prompt length.
    """
    import jax

    from repro.configs import get_reduced_config
    from repro.models.model_zoo import build_model
    from repro.serving.batching import ContinuousBatcher, Request

    cfg = get_reduced_config("qwen3_0_6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [
        RNG.integers(2, cfg.vocab, prompt_len).astype(np.int32)
        for _ in range(n_requests)
    ]

    results = {}
    outputs = {}
    for mode in ("token", "batched"):
        reqs = [
            Request(req_id=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)
        ]
        b = ContinuousBatcher(model, max_batch=4, max_len=32, prefill_mode=mode)
        b.model_params = params
        b.serve(list(reqs))  # warm the compiles
        for r in reqs:
            r.output, r.finished_step = [], -1
        t0 = time.perf_counter()
        m = b.serve(list(reqs))
        wall = time.perf_counter() - t0
        outputs[mode] = [list(r.output) for r in reqs]
        results[mode] = {
            "wall_s": wall,
            "tokens_per_s": m.tokens_out / wall,
            "prefill_calls": m.prefill_calls,
            "prompt_tokens": int(sum(len(p) for p in prompts)),
        }
        print(
            f"  {mode:8s}: wall={wall*1e3:7.1f}ms "
            f"decode_tokens/s={m.tokens_out / wall:7.0f} "
            f"prefill_calls={m.prefill_calls}"
        )
    assert outputs["token"] == outputs["batched"], (
        "batched prefill must produce identical decodes"
    )
    speedup = results["token"]["wall_s"] / results["batched"]["wall_s"]
    results["prefill_speedup"] = speedup
    print(f"  batched-prefill admission speedup: {speedup:.2f}x (identical outputs)")
    return results


def _drive_rounds(svc, reqs, max_rounds=10_000):
    """Closed-loop driver that records (completed, recoveries) after every
    scheduling round -- the per-round completion trajectory the chaos gates
    are computed from."""
    for r in reqs:
        svc.submit(r)
    hist = []
    while svc._busy():
        if len(hist) >= max_rounds:
            raise RuntimeError(f"service did not drain in {max_rounds} rounds")
        svc.step()
        m = svc.metrics
        hist.append((int(m.completed), int(m.recoveries)))
    svc.close()
    svc._drain_emit()
    hist.append((int(svc.metrics.completed), int(svc.metrics.recoveries)))
    return hist


def bench_chaos(
    n_requests=360, n_keys=256, slots=8, quantum=6, wave=8,
    kill_call=60, kill_shard=3, recovery_window=12, seed=42, check=False,
):
    """Kill-one-shard-mid-stream under the full fault-tolerant serving stack.

    An 8-shard meshed engine serves a mixed read/write stream (every 4th
    request an insert) twice from identical pre-states: a failure-free
    reference, then a run where ``kill_shard`` dies at engine call
    ``kill_call``.  Gates (``--check``):

      * exactly one recovery; degraded-mode retries observed;
      * zero acknowledged commits lost -- the recovered run's final arena
        (data + heap) and every request's (status, result) are bit-identical
        to the failure-free reference;
      * throughput recovers: mean completions/round over the
        ``recovery_window`` rounds after service resumes >= 90% of the
        pre-fault rate, and service resumes within a bounded number of
        rounds of the fault.
    """
    import tempfile

    from repro.core.faults import FaultInjector, FaultPlan
    from repro.distributed.arena_ft import ArenaStore, FaultToleranceConfig

    rng = np.random.default_rng(seed)
    keys = np.arange(100, 100 + n_keys, dtype=np.int32)
    # one blueprint, materialized fresh per run: the reference and chaos
    # runs must see byte-identical workloads (requests mutate in place)
    read_keys = [int(keys[int(rng.integers(0, n_keys))]) for _ in range(n_requests)]

    def serve(tmp, plan):
        b = ArenaBuilder(4 * n_keys, 4, num_shards=P, policy="interleaved")
        head = linked_list.build_into(b, keys, keys * 2)
        inj = FaultInjector(plan) if plan is not None else None
        eng = PulseEngine(
            b.finish(), mesh=mem_mesh(P), fault_injector=inj
        )
        ft = FaultToleranceConfig(store=ArenaStore(tmp))
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
            slots_per_structure=slots,
            quantum=quantum,
            pipeline="async",
            fault_tolerance=ft,
        )
        reqs = []
        for i in range(n_requests):
            if i % 4 == 2:
                reqs.append(
                    TraversalRequest(
                        i, "list_ins", 10_000 + i, value=i * 13,
                        tenant="writer", arrive_round=i // wave,
                    )
                )
            else:
                reqs.append(
                    TraversalRequest(
                        i, "list", read_keys[i],
                        tenant="reader", arrive_round=i // wave,
                    )
                )
        hist = _drive_rounds(svc, reqs)
        ft.store.close()
        return reqs, svc.metrics, eng.arena, hist

    plan = FaultPlan(
        kill_shard=kill_shard, kill_call=kill_call, kill_superstep=2
    )
    with tempfile.TemporaryDirectory() as d0, tempfile.TemporaryDirectory() as d1:
        t0 = time.perf_counter()
        r_ref, m_ref, ar_ref, hist_ref = serve(d0, None)
        t_ref = time.perf_counter() - t0
        t0 = time.perf_counter()
        r_kill, m_kill, ar_kill, hist_kill = serve(d1, plan)
        t_kill = time.perf_counter() - t0

    assert m_ref.recoveries == 0 and m_ref.retries == 0
    assert m_kill.completed == m_ref.completed == n_requests

    # zero acknowledged commits lost: bit-identical arena + results
    arena_identical = bool(
        np.array_equal(np.asarray(ar_ref.data), np.asarray(ar_kill.data))
        and np.array_equal(np.asarray(ar_ref.heap), np.asarray(ar_kill.heap))
    )
    results_identical = all(
        a.status == b.status and np.array_equal(a.result, b.result)
        for a, b in zip(r_ref, r_kill)
    )

    # per-round completion deltas; the fault round is where recoveries flips
    done = np.asarray([c for c, _ in hist_kill])
    rec = np.asarray([v for _, v in hist_kill])
    delta = np.diff(np.concatenate([[0], done]))
    fault_round = int(np.argmax(rec > 0)) if (rec > 0).any() else -1
    pre_rate = float(delta[:fault_round].mean()) if fault_round > 0 else 0.0
    # completion granularity: a request retires only after ~depth/quantum
    # quanta, so both the resume bound and the measurement window must cover
    # at least one full request lifetime plus backoff slack
    depth_quanta = -(-n_keys // quantum)
    lag_bound = depth_quanta + 8
    win = max(recovery_window, lag_bound)
    # service resumes at the first post-fault round that retires anything
    # (the failed group sits out its backoff, then in-flight re-execution
    # must finish a request's remaining quanta)
    post = np.nonzero(delta[fault_round + 1:])[0]
    resume_round = fault_round + 1 + int(post[0]) if len(post) else -1
    window = delta[resume_round: resume_round + win]
    post_rate = float(window.mean()) if len(window) else 0.0
    ratio = post_rate / pre_rate if pre_rate > 0 else 0.0
    resume_lag = resume_round - fault_round if resume_round >= 0 else -1

    print(
        f"  reference : rounds={m_ref.rounds} commits={m_ref.commits} "
        f"wall={t_ref:.1f}s"
    )
    print(
        f"  chaos     : rounds={m_kill.rounds} commits={m_kill.commits} "
        f"recoveries={m_kill.recoveries} replayed={m_kill.replayed_commits} "
        f"retries={m_kill.retries} mean_recovery={m_kill.mean_recovery_ms:.0f}ms "
        f"wall={t_kill:.1f}s"
    )
    print(
        f"  fault@round {fault_round}, resumed +{resume_lag} rounds: "
        f"pre-fault {pre_rate:.2f} req/round -> "
        f"post-recovery {post_rate:.2f} req/round ({ratio:.0%})"
    )
    print(
        f"  acked-commit safety: arena {'identical' if arena_identical else 'DIVERGED'}, "
        f"results {'identical' if results_identical else 'DIVERGED'}"
    )
    if check:
        assert m_kill.recoveries == 1, m_kill.recoveries
        assert m_kill.retries > 0, "degraded mode must re-queue hit requests"
        assert arena_identical, "recovery lost acknowledged commits (arena)"
        assert results_identical, "recovery changed request results"
        assert 0 <= resume_lag <= lag_bound, (
            f"service must resume within {lag_bound} rounds of the fault "
            f"(one request lifetime + backoff), took {resume_lag}"
        )
        assert ratio >= 0.9, (
            f"post-recovery throughput must reach >=90% of pre-fault "
            f"within {win} rounds, got {ratio:.0%}"
        )
    return {
        "n_requests": int(n_requests),
        "kill_shard": int(kill_shard),
        "kill_call": int(kill_call),
        "recoveries": int(m_kill.recoveries),
        "replayed_commits": int(m_kill.replayed_commits),
        "retries": int(m_kill.retries),
        "retry_exhausted": int(m_kill.retry_exhausted),
        "mean_recovery_ms": float(m_kill.mean_recovery_ms),
        "fault_round": fault_round,
        "resume_lag_rounds": int(resume_lag),
        "pre_fault_rate": pre_rate,
        "post_recovery_rate": post_rate,
        "recovery_ratio": float(ratio),
        "recovery_window_rounds": int(win),
        "resume_lag_bound_rounds": int(lag_bound),
        "zero_acked_commits_lost": bool(arena_identical and results_identical),
        "reference_rounds": int(m_ref.rounds),
        "chaos_rounds": int(m_kill.rounds),
        "reference_wall_s": float(t_ref),
        "chaos_wall_s": float(t_kill),
    }


def bench_reshard(
    n_requests=400, n_keys=256, slots=8, quantum=6, wave=8,
    reshard_round=6, recovery_window=12, seed=42, check=False,
):
    """Live 2x reshard (4 -> 8 shards) mid-stream under load.

    A 4-shard meshed engine serves a mixed BST find/update stream (updates
    are alloc-free, so committed state is partition-independent); at
    scheduling round ``reshard_round`` the service is asked to double its
    shard count online (drain in-flight quanta -> remap -> new mesh ->
    resume).  A cold run serves the same stream at 8 shards from the start,
    seeded from the offline ``remap_shards`` of the identical 4-shard build
    -- the partition the live path must converge to.  Gates (``--check``):

      * exactly one reshard; the drain is bounded;
      * bit-identical to the cold 8-shard run: every request's (status,
        result), the arena payload (data/bounds/perms), the allocator
        registers (free head + bump frontier), and the commit count;
      * throughput recovers: mean completions/round over the
        ``recovery_window`` rounds after serving resumes >= 90% of the
        pre-reshard rate.
    """
    from repro.core.arena import remap_shards
    from repro.core.structures import bst

    rng = np.random.default_rng(seed)
    keys = np.arange(100, 100 + n_keys, dtype=np.int32)
    read_keys = [int(keys[int(rng.integers(0, n_keys))]) for _ in range(n_requests)]
    upd_keys = [int(keys[int(rng.integers(0, n_keys))]) for _ in range(n_requests)]

    def build4():
        b = ArenaBuilder(4 * n_keys, 4, num_shards=4, policy="interleaved")
        root, _h = bst.build_into(b, keys, keys * 2)
        return b.finish(), root

    def serve(arena, root, nshards, reshard_at=None):
        mesh = mem_mesh(nshards)
        eng = PulseEngine(arena, mesh=mesh)
        svc = PulseService(
            eng,
            {
                "bst": StructureSpec(bst.find_iterator(), (root,), group="bst"),
                "bst_upd": StructureSpec(
                    bst.update_iterator(), (root,), group="bst", takes_value=True
                ),
            },
            slots_per_structure=slots,
            quantum=quantum,
            pipeline="async",
        )
        reqs = []
        for i in range(n_requests):
            if i % 4 == 3:
                reqs.append(
                    TraversalRequest(
                        i, "bst_upd", upd_keys[i], value=9000 + i,
                        tenant="writer", arrive_round=i // wave,
                    )
                )
            else:
                reqs.append(
                    TraversalRequest(
                        i, "bst", read_keys[i],
                        tenant="reader", arrive_round=i // wave,
                    )
                )
        for r in reqs:
            svc.submit(r)
        hist = []
        try:
            while svc._busy():
                if reshard_at is not None and svc.metrics.rounds == reshard_at:
                    svc.request_reshard(2 * nshards)
                if len(hist) >= 10_000:
                    raise RuntimeError("service did not drain in 10000 rounds")
                svc.step()
                m = svc.metrics
                hist.append((int(m.completed), int(m.reshards)))
        finally:
            svc.close()
            svc._drain_emit()
        hist.append((int(svc.metrics.completed), int(svc.metrics.reshards)))
        return reqs, svc.metrics, eng.arena, hist

    a4, root = build4()
    t0 = time.perf_counter()
    r_cold, m_cold, ar_cold, _ = serve(remap_shards(a4, 8), root, 8)
    t_cold = time.perf_counter() - t0
    a4b, root_b = build4()
    assert root_b == root
    t0 = time.perf_counter()
    r_live, m_live, ar_live, hist = serve(a4b, root, 4, reshard_at=reshard_round)
    t_live = time.perf_counter() - t0

    assert m_live.completed == m_cold.completed == n_requests

    results_identical = all(
        a.status == b.status and np.array_equal(a.result, b.result)
        for a, b in zip(r_cold, r_live)
    )
    # payload + partition tables + allocator registers (free head, bump
    # frontier); epoch/commit heap counters are commit-placement metadata
    # that legitimately differs when early quanta committed at 4 shards
    arena_identical = bool(
        np.array_equal(np.asarray(ar_cold.data), np.asarray(ar_live.data))
        and np.array_equal(np.asarray(ar_cold.bounds), np.asarray(ar_live.bounds))
        and np.array_equal(np.asarray(ar_cold.perms), np.asarray(ar_live.perms))
        and np.array_equal(
            np.asarray(ar_cold.heap)[:, :2], np.asarray(ar_live.heap)[:, :2]
        )
    )

    done = np.asarray([c for c, _ in hist])
    rs = np.asarray([v for _, v in hist])
    delta = np.diff(np.concatenate([[0], done]))
    cut_round = int(np.argmax(rs > 0)) if (rs > 0).any() else -1
    pre_rate = float(delta[:cut_round].mean()) if cut_round > 0 else 0.0
    post = np.nonzero(delta[cut_round + 1:])[0]
    resume_round = cut_round + 1 + int(post[0]) if len(post) else -1
    window = delta[resume_round: resume_round + recovery_window]
    post_rate = float(window.mean()) if len(window) else 0.0
    ratio = post_rate / pre_rate if pre_rate > 0 else 0.0
    resume_lag = resume_round - cut_round if resume_round >= 0 else -1

    print(
        f"  cold 8-shard : rounds={m_cold.rounds} commits={m_cold.commits} "
        f"wall={t_cold:.1f}s"
    )
    print(
        f"  live 4->8    : rounds={m_live.rounds} commits={m_live.commits} "
        f"reshards={m_live.reshards} drain={m_live.reshard_drain_rounds} "
        f"wall={t_live:.1f}s"
    )
    print(
        f"  cutover@round {cut_round}, resumed +{resume_lag} rounds: "
        f"pre-reshard {pre_rate:.2f} req/round -> "
        f"post-cutover {post_rate:.2f} req/round ({ratio:.0%})"
    )
    print(
        f"  cold-equivalence: arena {'identical' if arena_identical else 'DIVERGED'}, "
        f"results {'identical' if results_identical else 'DIVERGED'}"
    )
    if check:
        assert m_live.reshards == 1, m_live.reshards
        assert arena_identical, "live reshard diverged from the cold 8-shard run"
        assert results_identical, "live reshard changed request results"
        assert m_live.commits == m_cold.commits > 0, (
            m_live.commits, m_cold.commits,
        )
        assert ratio >= 0.9, (
            f"post-reshard throughput must reach >=90% of the pre-reshard "
            f"rate within {recovery_window} rounds, got {ratio:.0%}"
        )
    return {
        "n_requests": int(n_requests),
        "reshard_round": int(reshard_round),
        "reshards": int(m_live.reshards),
        "drain_rounds": int(m_live.reshard_drain_rounds),
        "cutover_round": int(cut_round),
        "resume_lag_rounds": int(resume_lag),
        "pre_reshard_rate": pre_rate,
        "post_cutover_rate": post_rate,
        "recovery_ratio": float(ratio),
        "recovery_window_rounds": int(recovery_window),
        "commits": int(m_live.commits),
        "bit_identical_to_cold": bool(arena_identical and results_identical),
        "cold_rounds": int(m_cold.rounds),
        "live_rounds": int(m_live.rounds),
        "cold_wall_s": float(t_cold),
        "live_wall_s": float(t_live),
    }


def main(argv=None):
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--json",
        nargs="?",
        const="BENCH_service.json",
        default=None,
        metavar="PATH",
        help="write machine-readable results (default path: BENCH_service.json)",
    )
    ap.add_argument(
        "--small",
        action="store_true",
        help="CI smoke sizes (faster, same assertions)",
    )
    ap.add_argument(
        "--arrival",
        default=None,
        metavar="SPEC",
        help="open-loop arrival process, e.g. 'poisson:500' (500 req/s "
        "offered); sets the matched-load rate for the async-vs-sync "
        "experiment and switches the mixed-workload experiment off "
        "closed-loop rounds",
    )
    ap.add_argument(
        "--seed",
        type=int,
        default=42,
        help="seed for arrival-trace and workload generation (recorded in "
        "the JSON artifact so overload runs are reproducible)",
    )
    ap.add_argument(
        "--check",
        action="store_true",
        help="enforce the serving gates: async >= 1.3x sync throughput with "
        "p99 <= 1.1x at matched load, async saturation >= 2x sync "
        "(--chaos: recovery + zero-acked-loss + throughput-recovery gates)",
    )
    ap.add_argument(
        "--chaos",
        action="store_true",
        help="chaos mode only: kill one shard mid-stream under the "
        "fault-tolerant serving stack and gate recovery (skips the four "
        "standard experiments; pair with --json BENCH_chaos.json)",
    )
    ap.add_argument(
        "--reshard",
        action="store_true",
        help="reshard mode only: live 4 -> 8 shard change mid-stream, gated "
        "on bit-identity to a cold 8-shard run + >=90%% throughput "
        "recovery (skips the four standard experiments; pair with "
        "--json BENCH_reshard.json)",
    )
    args = ap.parse_args(argv)
    arrival = parse_arrival(args.arrival)

    if args.chaos:
        print("[1/1] chaos: kill-one-shard-mid-stream recovery")
        rc = bench_chaos(
            seed=args.seed,
            check=args.check,
            **(
                {"n_requests": 120, "n_keys": 64, "kill_call": 24}
                if args.small
                else {}
            ),
        )
        print("\nsummary:", rc)
        if args.json:
            payload = {
                "benchmark": "service_bench_chaos",
                "config": {
                    "shards": P,
                    "small": bool(args.small),
                    "seed": int(args.seed),
                    "checked": bool(args.check),
                },
                "chaos": rc,
            }
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"wrote {args.json}")
        return

    if args.reshard:
        print("[1/1] reshard: live 4 -> 8 shard change mid-stream")
        rr = bench_reshard(
            seed=args.seed,
            check=args.check,
            **(
                {"n_requests": 120, "n_keys": 64, "reshard_round": 4}
                if args.small
                else {}
            ),
        )
        print("\nsummary:", rr)
        if args.json:
            payload = {
                "benchmark": "service_bench_reshard",
                "config": {
                    "shards": P,
                    "small": bool(args.small),
                    "seed": int(args.seed),
                    "checked": bool(args.check),
                },
                "reshard": rr,
            }
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"wrote {args.json}")
        return

    print("[1/4] compacted supersteps vs bulk-synchronous baseline")
    r1 = bench_compacted_routing(
        **({"n": 512, "B": 128} if args.small else {})
    )
    matched_rps = arrival[1] if arrival else 300.0
    print(
        f"[2/4] PulseService: async pipeline vs sync loop "
        f"(open-loop poisson:{matched_rps:.0f}, seed={args.seed})"
    )
    r2 = bench_async_pipeline(
        matched_rps,
        seed=args.seed,
        check=args.check,
        **(
            {"n_requests": 120, "sweep_requests": 60, "max_quantum": 128}
            if args.small
            else {}
        ),
    )
    print(
        "[3/4] PulseService: mixed 4-structure workload"
        + (f" (open-loop {args.arrival})" if arrival else "")
    )
    r3 = bench_service(
        arrival=arrival,
        seed=args.seed,
        **({"n_requests": 150, "slots": 32} if args.small else {}),
    )
    print("[4/4] LM admission: batched prefill vs token-by-token")
    r4 = bench_batched_prefill(
        **({"n_requests": 8, "prompt_len": 6, "max_new": 4} if args.small else {})
    )
    summary = {
        **r1,
        **r3,
        "async_speedup": r2["throughput_speedup"],
        "async_p99_ratio": r2["p99_ratio"],
        "sync_saturation_rps": r2["sync_saturation_rps"],
        "async_saturation_rps": r2["async_saturation_rps"],
        "prefill_speedup": r4["prefill_speedup"],
    }
    print("\nsummary:", summary)
    if args.json:
        payload = {
            "benchmark": "service_bench",
            "config": {
                "shards": P,
                "small": bool(args.small),
                "seed": int(args.seed),
                "checked": bool(args.check),
            },
            "compacted_routing": r1,
            "async_pipeline": r2,
            "service": r3,
            "batched_prefill": r4,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
