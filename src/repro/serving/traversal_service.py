"""PulseService: continuous-batching front end for pointer traversals.

The repo's engine exposes one-shot ``PulseEngine.execute`` calls; this module
turns it into a *serving system* in the style of the vLLM-ish token loop in
``serving/batching.py``, but for the paper's workload -- heterogeneous
traversal requests (list walk, BST/B-tree lookup, skiplist search, hash-chain
probe) arriving from many tenants:

  * **slot groups** -- a SIMD batch executes one iterator program, so each
    registered structure owns a fixed group of slots; all groups share one
    admission queue.
  * **continuous batching via continuations** -- each scheduling round runs
    every occupied group for a ``quantum`` of iterations.  Requests that
    finish retire and free their slot *immediately* (backfilled in the same
    round); unfinished requests come back as STATUS_MAXED continuations --
    ``(cur_ptr, scratch_pad)`` is the complete traversal state (paper S3/S5),
    so resuming them next round is exactly the paper's "continuing stateful
    iterator execution", repurposed as a preemption mechanism.
  * **admission** -- per-tenant queues with deadline-aware (EDF) scheduling
    and fairness credits (``serving/admission.py``).
  * **accounting** -- p50/p99 latency, throughput, deadline hit rate,
    per-tenant breakdowns, plus the engine-side stats (supersteps, wire
    words, wave-scheduler savings) aggregated over the run.

The service runs identically over the engine's local XLA path, the
pulse_chase kernel path (``backend="kernel"``), and the distributed
superstep path (engine constructed with a mesh) -- admission is above the
dispatch decision, like the paper's CPU node.

**Write tenants** -- a spec whose iterator mutates (inserts/deletes/updates,
``StructureSpec.writes``) is admitted under a per-structure-group barrier
(``admission.apply_write_barriers``): a write batch owns its group
exclusively, queued writers drain readers out first, and the engine's
resident arena is swapped to the post-commit state after every mutating
quantum -- so the next round's reads (any group) traverse the updated heap.
"""

from __future__ import annotations

import dataclasses
import random
import time
from collections import deque

import numpy as np

import jax.numpy as jnp

from repro.core import routing
from repro.core.arena import NULL, remap_shards
from repro.core.engine import PulseEngine
from repro.core.faults import ShardFailure
from repro.core.iterator import (
    STATUS_ACTIVE,
    STATUS_DONE,
    STATUS_FAULT,
    STATUS_MAXED,
    STATUS_RETRY,
    STATUS_SHED,
    PulseIterator,
)
from repro.core.trace import TRACE
from repro.distributed.sharding import mem_mesh
from repro.serving.admission import (
    AdmissionController,
    TenantRateLimiter,
    TraversalRequest,
    apply_write_barriers,
)
from repro.serving.batching import DeviceRunner, QuantumWork

__all__ = [
    "PulseService",
    "StructureSpec",
    "ServiceMetrics",
    # status re-exports: these historically lived here; core.iterator is now
    # the single home for every STATUS_* constant
    "STATUS_SHED",
    "STATUS_RETRY",
]


@dataclasses.dataclass(frozen=True)
class StructureSpec:
    """A servable structure: the iterator program + its fixed init arguments
    (root pointer, bucket heads, ...).  ``init`` is called per admission
    batch with the admitted queries.

    ``group`` names the structure *family* the spec operates on (defaults to
    the spec's registered name): a mutating spec ("list_insert") and the
    read spec over the same heap region ("list") share a group, and the
    admission barrier gives writers the group exclusively
    (``admission.apply_write_barriers``).  Mutability is derived from the
    iterator itself."""

    iterator: PulseIterator
    init_args: tuple = ()
    group: str | None = None
    # True for specs whose init() takes (keys, values, ...) -- inserts and
    # updates consume the request's write payload (TraversalRequest.value)
    takes_value: bool = False

    @property
    def writes(self) -> bool:
        return self.iterator.mutates


@dataclasses.dataclass
class ServiceMetrics:
    rounds: int = 0
    engine_calls: int = 0
    retired: int = 0  # every request that left its slot, any status
    completed: int = 0  # retired successfully (DONE only)
    faulted: int = 0
    timed_out: int = 0  # retired at max_request_iters
    wall_s: float = 0.0
    lane_iters: int = 0  # productive iterations executed
    latencies_ms: list = dataclasses.field(default_factory=list)
    per_tenant: dict = dataclasses.field(default_factory=dict)
    deadlines_met: int = 0
    deadlines_missed: int = 0
    # engine-side aggregates (distributed path only)
    supersteps: int = 0
    wire_words: int = 0
    # write path: mutations committed + requests served by mutating specs
    commits: int = 0
    writes_retired: int = 0
    # overload + pipeline accounting
    shed: int = 0  # arrivals rejected (rate limit or bounded queue)
    preempted: int = 0  # continuations evicted for an urgent deadline
    queue_depth_max: int = 0  # admission-queue high-water mark
    quantum_min_used: int = 0  # smallest / largest quantum any round ran
    quantum_max_used: int = 0
    # fault tolerance (chaos runs): shard deaths recovered from, commits
    # replayed out of the durable log, requests re-queued off dead shards
    recoveries: int = 0
    replayed_commits: int = 0
    retries: int = 0
    retry_exhausted: int = 0  # requests retired STATUS_RETRY (budget spent)
    recovery_ms_total: float = 0.0
    # replication + elasticity: read quanta that fanned out to a replica
    # while a primary was dead, write quanta shipped to the hot standby,
    # watchdog probe accounting, and completed live reshards
    failover_quanta: int = 0
    replica_quanta: int = 0
    watchdog_probes: int = 0
    watchdog_suspects: int = 0
    reshards: int = 0
    reshard_drain_rounds: int = 0
    # where quanta ran: ExecResult.path -> count
    quanta_by_path: dict = dataclasses.field(default_factory=dict)

    def _pct(self, p: float) -> float:
        if not self.latencies_ms:
            return float("nan")
        return float(np.percentile(np.asarray(self.latencies_ms), p))

    @property
    def cpu_node_quanta(self) -> int:
        """Quanta the dispatch cost model sent to the host traversal
        (``cpu_node_execute``, ``ExecResult.offloaded=False``)."""
        return self.quanta_by_path.get("cpu_node", 0)

    @property
    def p50_ms(self) -> float:
        return self._pct(50)

    @property
    def p99_ms(self) -> float:
        return self._pct(99)

    @property
    def p999_ms(self) -> float:
        return self._pct(99.9)

    @property
    def throughput_rps(self) -> float:
        return self.completed / self.wall_s if self.wall_s else 0.0

    @property
    def mean_recovery_ms(self) -> float:
        if not self.recoveries:
            return float("nan")
        return self.recovery_ms_total / self.recoveries

    @property
    def deadline_hit_rate(self) -> float:
        n = self.deadlines_met + self.deadlines_missed
        return self.deadlines_met / n if n else float("nan")

    def summary(self) -> str:
        return (
            f"retired={self.retired} completed={self.completed} "
            f"faulted={self.faulted} "
            f"timed_out={self.timed_out} rounds={self.rounds} "
            f"p50={self.p50_ms:.2f}ms p99={self.p99_ms:.2f}ms "
            f"throughput={self.throughput_rps:.0f} req/s shed={self.shed}"
        )


def _make_probe_iterator() -> PulseIterator:
    """One-touch read iterator for the shard watchdog: loads a single node
    from a chosen shard's range and finishes.  It runs through the same
    dispatched superstep path as real traffic, so a delay-faulted straggler
    stalls the probe for its full injected latency -- exactly the signal the
    watchdog escalates to suspected-dead (a straggler never raises
    ``ShardFailure`` on its own; this closes that blind spot)."""

    def end_fn(node, ptr, scr):
        return jnp.bool_(True), scr.at[0].set(node[0])

    def next_fn(node, ptr, scr):
        return jnp.int32(NULL), scr

    return PulseIterator(
        scratch_words=1, next_fn=next_fn, end_fn=end_fn, name="shard_probe"
    )


_PROBE_IT = _make_probe_iterator()


class _SlotGroup:
    """Fixed-width slot block for one structure (one compiled batch shape)."""

    def __init__(self, name: str, spec: StructureSpec, n_slots: int):
        self.name = name
        self.spec = spec
        self.n_slots = n_slots
        S = spec.iterator.scratch_words
        self.req: list[TraversalRequest | None] = [None] * n_slots
        self.ptr = np.full(n_slots, NULL, np.int32)
        self.scratch = np.zeros((n_slots, S), np.int32)
        self.iters = np.zeros(n_slots, np.int64)
        # fault tolerance: a group whose quantum hit a dead shard is parked
        # (occupants kept, admission blocked) until this round; consecutive
        # failures drive the exponential backoff
        self.backoff_until = -1
        self.fail_streak = 0

    def free_slots(self) -> int:
        return sum(r is None for r in self.req)

    def occupied(self) -> np.ndarray:
        return np.array([r is not None for r in self.req])


class PulseService:
    """Continuous-batching traversal server over a PulseEngine."""

    def __init__(
        self,
        engine: PulseEngine,
        structures: dict[str, StructureSpec],
        *,
        slots_per_structure: int = 32,
        quantum: int = 16,
        max_request_iters: int = 1 << 16,
        backend: str = "xla",
        compact: bool = True,
        fused: bool = True,
        schedule: str = "auto",
        fabric: str = "dense",
        pipeline: str = "sync",
        runner_depth: int = 2,
        min_quantum: int | None = None,
        max_quantum: int | None = None,
        slo_safety: float = 0.5,
        preempt: bool = False,
        max_pending: int | None = None,
        rate_limit_rps: float | None = None,
        rate_limit_burst: float | None = None,
        fault_tolerance=None,
    ):
        if quantum < 1:
            raise ValueError("quantum must be >= 1")
        if pipeline not in ("sync", "async"):
            raise ValueError(f"pipeline must be 'sync' or 'async', got {pipeline!r}")
        self.engine = engine
        self.backend = backend
        self.compact = compact
        # fused quanta share one compiled whole-traversal executable per
        # (structure, slot shape) and reuse the device-resident arena, so
        # steady-state rounds neither retrace nor re-upload the heap
        self.fused = fused
        # "auto" resolves per-iterator through the dispatch engine's overlap
        # model -- normally the wavefront-pipelined schedule, which overlaps
        # the in-flight wavefront's collective with resident local chasing
        self.schedule = schedule
        self.fabric = fabric
        self.quantum = quantum
        self.max_request_iters = max_request_iters
        # pipeline="async": a background DeviceRunner thread keeps the
        # current quantum in flight while this thread drains emit events and
        # books the next round's admissions.  Engine calls stay strictly
        # FIFO on the runner, so results/commits/arenas are bit-identical to
        # the synchronous loop under the same quantum policy.
        self.pipeline = pipeline
        self.runner_depth = runner_depth
        self._runner: DeviceRunner | None = None
        # SLO-aware quantum sizing: rounds run [min_quantum, max_quantum]
        # iterations, grown multiplicatively while no deadline is at risk
        # and shrunk to fit the earliest deadline's headroom (EWMA ms/iter
        # estimate).  Defaults (None) pin both bounds to ``quantum`` --
        # i.e. the legacy fixed-quantum behavior.
        self.min_quantum = min_quantum if min_quantum is not None else quantum
        self.max_quantum = max_quantum if max_quantum is not None else quantum
        if not 1 <= self.min_quantum <= self.max_quantum:
            raise ValueError("need 1 <= min_quantum <= max_quantum")
        self.slo_safety = slo_safety
        self._cur_quantum = min(max(quantum, self.min_quantum), self.max_quantum)
        self._ms_per_iter: float | None = None
        # EDF preemption: an urgent queued deadline may evict a MAXED
        # continuation (its (ptr, scratch) is complete traversal state)
        # from a full read group; the evictee requeues at its original
        # arrival order and resumes where it stopped.
        self.preempt = preempt
        # Admission-time static verification (pulse-verify): an ISA-backed
        # spec whose iterator carries no certificate is verified HERE --
        # before any slot group exists, so an unsafe tenant program is
        # rejected with instruction-level diagnostics rather than faulting
        # mid-traversal on a remote shard.  Iterators built through
        # ``isa.as_pulse_iterator`` arrive already certified (facts set) and
        # skip the re-analysis; hand-written JAX iterators have no Program
        # to analyze and stay under the conservative runtime checks.
        for name, spec in structures.items():
            self._verify_spec(name, spec)
        # the single-node kernel path runs a read iterator's body in the
        # lane-columnar form Mosaic compiles; one it cannot express (the
        # ISA VM's while loop over a program table) is refused here, before
        # anything is enqueued, not when the first quantum traces
        if backend == "kernel" and not (
            engine.mesh is not None and engine.arena.num_shards > 1
        ):
            from repro.kernels.pulse_chase import ops as chase_ops

            for name, spec in structures.items():
                if not spec.writes:
                    try:
                        chase_ops.check_lane_columns(
                            spec.iterator, engine.arena.node_words
                        )
                    except NotImplementedError as e:
                        raise NotImplementedError(
                            f"structure {name!r} cannot be served with "
                            f"backend='kernel': {e}"
                        ) from None
        self.groups = {
            name: _SlotGroup(name, spec, slots_per_structure)
            for name, spec in structures.items()
        }
        limiter = (
            TenantRateLimiter(rate_limit_rps, rate_limit_burst)
            if rate_limit_rps is not None
            else None
        )
        self.admission = AdmissionController(
            max_pending=max_pending, rate_limiter=limiter
        )
        self.metrics = ServiceMetrics()
        # fault tolerance (arena_ft.FaultToleranceConfig): snapshot + commit
        # log durability for write quanta, shard-failure detection, and
        # degraded-mode serving (backoff + retry budget) while recovering
        self.ft = fault_tolerance
        self._detector = None
        self._dead_until: dict[int, int] = {}  # shard -> revive round
        self._ft_rng = None
        self._writes_since_snapshot = 0
        # hot-shard replication (arena_ft.ReplicationConfig): a log-shipped
        # standby mirrors designated shards; reads fan out to it when a
        # primary dies (or always, under policy="spread")
        self._replicas = None
        # per-quantum shard watchdog (ft.watchdog_timeout_s > 0): probes on
        # a logical round clock catch stragglers that never raise
        self._watchdog = None
        self._wd_round = -1
        if self.ft is not None:
            from repro.distributed.arena_ft import ReplicaSet
            from repro.distributed.elastic import (
                HeartbeatMonitor,
                ShardFailureDetector,
            )

            for name, spec in structures.items():
                if spec.writes:
                    self.ft.store.register_iterator(name, spec.iterator)
            # recovery always needs an anchor state to replay from
            self.ft.store.ensure_baseline(engine.arena)
            self._detector = ShardFailureDetector(engine.arena.num_shards)
            self._ft_rng = random.Random(self.ft.seed)
            rep = getattr(self.ft, "replication", None)
            if rep is not None:
                if engine.mesh is None or engine.arena.num_shards < 2:
                    raise ValueError(
                        "replication needs a distributed engine (mesh) with "
                        ">= 2 shards"
                    )
                plan = routing.make_replica_plan(
                    engine.arena.num_shards, rep.primaries, policy=rep.policy
                )
                self._replicas = ReplicaSet(plan, engine.arena)
            if getattr(self.ft, "watchdog_timeout_s", 0.0) > 0:
                if engine.mesh is None or engine.arena.num_shards < 2:
                    raise ValueError(
                        "the shard watchdog needs a distributed engine (mesh)"
                    )
                # timeout of one round on the logical clock = a shard is
                # suspected only after TWO consecutive slow probes -- one
                # transient scheduling hiccup on a loaded host never
                # degrades a healthy shard
                self._watchdog = HeartbeatMonitor(
                    engine.arena.num_shards,
                    timeout_s=1,
                    clock=lambda: self._wd_round,
                )
        # live resharding: owner-function epochs + the drain/cutover planner
        from repro.distributed.elastic import ReshardPlanner
        from repro.distributed.sharding import VersionedOwnerMap

        self._owner_map = VersionedOwnerMap(np.asarray(engine.arena.bounds))
        self._reshard = ReshardPlanner()
        self._pending_arrivals: list[TraversalRequest] = []
        # retirement events (writes?, request) pushed by whichever thread
        # retires; accounting drains them on the main thread
        self._emit: deque = deque()
        if self._watchdog is not None:
            # compile + warm the probe path so the first timed watchdog
            # round does not read XLA compile time as a stall
            for s in range(engine.arena.num_shards):
                self._probe_shard(s, warm=True)

    # ------------------------------ intake -----------------------------------

    @staticmethod
    def _verify_spec(name: str, spec: StructureSpec) -> None:
        """Reject-before-enqueue: statically verify an ISA-backed spec.

        A ``PulseIterator`` built by ``isa.as_pulse_iterator`` already went
        through pulse-verify (``facts`` is set) -- nothing to do.  One built
        around a raw ``Program`` some other way (facts absent but a
        ``__wrapped_program__`` attached to its step/mut function) is
        verified now; rejection raises the verifier's ``VerifyError`` --
        structured, instruction-pointed diagnostics under ``.diagnostics``
        -- annotated with the structure name, and the service never
        constructs a slot group for it.
        """
        it = spec.iterator
        if it.facts is not None:
            return
        prog = None
        for fn in (it.step_fn, it.mut_fn):
            prog = getattr(fn, "__wrapped_program__", None)
            if prog is not None:
                break
        if prog is None:
            return  # hand-written JAX iterator: no Program to analyze
        from repro.core.verify import VerifyError, verify_program

        try:
            verify_program(prog)
        except VerifyError as e:
            raise VerifyError(
                f"{e.name} (registered as structure {name!r})", e.diagnostics
            ) from None

    def submit(self, req: TraversalRequest) -> None:
        """Queue a request for admission (arrive_round gates logical time)."""
        if req.structure not in self.groups:
            raise KeyError(f"unknown structure {req.structure!r}")
        self._pending_arrivals.append(req)

    # ------------------------------ serving ----------------------------------

    def _intake(self, now_s: float, rnd: int) -> None:
        arrivals = [r for r in self._pending_arrivals if r.arrive_round <= rnd]
        self._pending_arrivals = [
            r for r in self._pending_arrivals if r.arrive_round > rnd
        ]
        m = self.metrics
        for r in arrivals:
            if not self.admission.submit(r, now_s):
                r.status = STATUS_SHED
                m.shed += 1
        m.queue_depth_max = max(m.queue_depth_max, self.admission.pending())

    def _maybe_preempt(self, now_s: float) -> None:
        """EDF slot stealing: if the most urgent *queued* deadline targets a
        full read group holding a strictly-less-urgent resumable
        continuation, evict that continuation (its (cur_ptr, scratch_pad)
        is complete traversal state) and requeue it at its original arrival
        order.  At most one eviction per round."""
        peek = self.admission.peek_earliest_deadline()
        if peek is None:
            return
        urgent_dl, urgent = peek
        g = self.groups.get(urgent.structure)
        if g is None or g.spec.writes or g.free_slots() > 0:
            return  # write batches own their group; free slots need no theft
        victim, victim_dl = -1, -1.0
        for s, r in enumerate(g.req):
            if r is None or g.iters[s] <= 0:
                continue  # only continuations that already ran a quantum
            dl = (
                float("inf")
                if r.deadline_ms is None
                else r.arrival_s + r.deadline_ms / 1e3
            )
            if victim < 0 or dl > victim_dl:
                victim, victim_dl = s, dl
        if victim < 0 or victim_dl <= urgent_dl:
            return  # nobody on-device is less urgent than the queued head
        v = g.req[victim]
        if v.tenant == urgent.tenant and getattr(v, "_seq", 0) < getattr(
            urgent, "_seq", 0
        ):
            # per-tenant FIFO: the requeued victim would sit ahead of the
            # urgent request in its own tenant queue, so eviction cannot
            # help -- it would only thrash the slot
            return
        r = g.req[victim]
        r.cont_ptr = int(g.ptr[victim])
        r.cont_scratch = g.scratch[victim].copy()
        r.iters = int(g.iters[victim])
        r.preemptions += 1
        g.req[victim] = None
        g.ptr[victim] = NULL
        self.admission.requeue(r)
        self.metrics.preempted += 1

    def _admit(self, now_s: float, rnd: int) -> None:
        with TRACE.span("service.admit"):
            self._intake(now_s, rnd)
            if self.preempt:
                self._maybe_preempt(now_s)
            free = {name: g.free_slots() for name, g in self.groups.items()}
            # a group parked on a dead shard admits nobody until its backoff
            # expires: the retried batch must re-run with its composition intact
            # (identical batch -> identical allocation order -> bit-identical
            # post-recovery arena)
            for name, g in self.groups.items():
                if g.backoff_until > rnd:
                    free[name] = 0
            # write-path barrier: writers take their structure group exclusively
            free = apply_write_barriers(
                free,
                {n: g.spec.group or n for n, g in self.groups.items()},
                {n: g.spec.writes for n, g in self.groups.items()},
                {n: bool(g.occupied().any()) for n, g in self.groups.items()},
                # head-only pending: a writer buried behind its tenant's queued
                # reads must not block those reads (circular wait otherwise)
                self.admission.head_pending_by_structure(),
            )
            # one write per datum in flight: a writer whose key an occupied or
            # just-admitted writer of its group holds waits for that commit
            groups = self.groups

            def write_key(r):
                spec = groups[r.structure].spec
                return (spec.group or r.structure, r.query) if spec.writes else None

            busy = {
                write_key(r)
                for g in groups.values() if g.spec.writes
                for r in g.req if r is not None
            }
            admitted = self.admission.admit(free, write_key, busy)
            by_group: dict[str, list[TraversalRequest]] = {}
            for r in admitted:
                by_group.setdefault(r.structure, []).append(r)
            for name, reqs in by_group.items():
                g = self.groups[name]
                fresh = [r for r in reqs if r.cont_ptr is None]
                if fresh:
                    with TRACE.span("service.init"):
                        # init() runs eagerly: pad its batch to the group's slot
                        # count so every round reuses one compiled shape instead of
                        # compiling its ops for each new admitted-batch size
                        queries = np.zeros(g.n_slots, np.int32)
                        queries[: len(fresh)] = [r.query for r in fresh]
                        if g.spec.takes_value:
                            values = np.zeros(g.n_slots, np.int32)
                            values[: len(fresh)] = [r.value for r in fresh]
                            ptr0, scr0 = g.spec.iterator.init(
                                jnp.asarray(queries), jnp.asarray(values),
                                *g.spec.init_args,
                            )
                        else:
                            ptr0, scr0 = g.spec.iterator.init(
                                jnp.asarray(queries), *g.spec.init_args
                            )
                        TRACE.count("host_syncs", 2)
                        ptr0 = np.asarray(ptr0, np.int32)
                        scr0 = np.asarray(scr0, np.int32)
                free_idx = [i for i, r in enumerate(g.req) if r is None]
                fi = 0
                for j, r in enumerate(reqs):
                    s = free_idx[j]
                    g.req[s] = r
                    if r.cont_ptr is None:
                        g.ptr[s] = ptr0[fi]
                        g.scratch[s] = scr0[fi]
                        g.iters[s] = 0
                        fi += 1
                    else:  # preempted continuation: resume saved traversal state
                        g.ptr[s] = r.cont_ptr
                        g.scratch[s] = r.cont_scratch
                        g.iters[s] = r.iters
                        r.cont_ptr = None
                        r.cont_scratch = None
                    if r.admit_s < 0:
                        r.admit_s = now_s
                        r.admit_round = rnd

    def _fast_retire(
        self, g: _SlotGroup, slot: int, status: int, now_s: float, rnd: int
    ) -> None:
        """Free the slot and capture the result (runner-thread-safe part of
        retirement); accounting happens when ``_drain_emit`` consumes the
        event on the main thread."""
        r = g.req[slot]
        assert r is not None
        r.status = int(status)
        r.iters = int(g.iters[slot])
        r.result = g.scratch[slot].copy()
        r.finish_s = now_s
        r.finish_round = rnd
        g.req[slot] = None
        g.ptr[slot] = NULL
        self._emit.append((g.spec.writes, r))

    def _drain_emit(self) -> None:
        """Consume retirement events (emit is decoupled from the step loop:
        in async mode this overlaps the device's current quantum)."""
        with TRACE.span("service.emit"):
            m = self.metrics
            while True:
                try:
                    writes, r = self._emit.popleft()
                except IndexError:
                    return
                m.retired += 1
                m.writes_retired += int(writes)
                m.completed += int(r.status == STATUS_DONE)
                m.faulted += int(r.status == STATUS_FAULT)
                m.timed_out += int(r.status == STATUS_MAXED)
                m.retry_exhausted += int(r.status == STATUS_RETRY)
                m.latencies_ms.append(r.latency_ms)
                t = m.per_tenant.setdefault(
                    r.tenant, {"completed": 0, "latencies_ms": []}
                )
                t["completed"] += int(r.status == STATUS_DONE)
                t["latencies_ms"].append(r.latency_ms)
                met = r.deadline_met
                if met is not None:
                    if met:
                        m.deadlines_met += 1
                    else:
                        m.deadlines_missed += 1

    def _apply_result(self, g: _SlotGroup, occ, res, dt_s: float, rnd: int) -> None:
        with TRACE.span("service.apply"):
            now_s = time.perf_counter()
            m = self.metrics
            m.engine_calls += 1
            m.quanta_by_path[res.path] = m.quanta_by_path.get(res.path, 0) + 1
            g.fail_streak = 0  # a quantum landed: the group is healthy again
            stats = res.stats
            if stats is not None and hasattr(stats, "supersteps"):
                m.supersteps += stats.supersteps
                m.wire_words += stats.total_wire_words
                m.commits += getattr(stats, "commits", 0)
            iters_done = 0
            for s in np.flatnonzero(occ):
                g.ptr[s] = res.ptr[s]
                g.scratch[s] = res.scratch[s]
                lane = int(res.iters[s])
                g.iters[s] += lane
                m.lane_iters += lane
                iters_done = max(iters_done, lane)
                st = int(res.status[s])
                if st == STATUS_MAXED and g.iters[s] < self.max_request_iters:
                    continue  # continuation: stays in its slot, resumes next round
                self._fast_retire(g, int(s), st, now_s, rnd)
            if iters_done > 0 and dt_s > 0:
                est = dt_s * 1e3 / iters_done  # ms per iteration, EWMA-smoothed
                self._ms_per_iter = (
                    est
                    if self._ms_per_iter is None
                    else 0.7 * self._ms_per_iter + 0.3 * est
                )

    def _make_work(self, g: _SlotGroup, rnd: int, quantum: int) -> QuantumWork:
        # NULL pointers in padding (free) slots fault on the first iteration,
        # so a fixed-width batch costs one compiled shape per group.
        occ = g.occupied()
        log_writes = self.ft is not None and g.spec.writes
        rep = self._replicas

        def run():
            with TRACE.span("service.quantum", round=rnd):
                t0 = time.perf_counter()
                p0 = g.ptr.copy()
                s0 = g.scratch.copy()
                rep_ctx = None if g.spec.writes else self._replica_ctx()
                res = self.engine.execute(
                    g.spec.iterator,
                    p0.copy(),
                    s0.copy(),
                    max_iters=quantum,
                    backend=self.backend,
                    compact=self.compact,
                    fused=self.fused,
                    schedule=self.schedule,
                    fabric=self.fabric,
                    replication=rep_ctx,
                )
                fanned_out = rep_ctx is not None and bool(
                    np.asarray(rep_ctx.dead_mask).any()
                )
                shipped = False
                if log_writes:
                    # durability point: the quantum is acknowledged once its
                    # *inputs* are in the fsynced log (replaying them through
                    # the commit oracle reproduces the post-commit arena
                    # bit-for-bit); a crash before this line loses only an
                    # unacknowledged quantum.  engine.execute defaults
                    # k_local=4 -- recorded so replay runs the same chase depth.
                    store = self.ft.store
                    seq = store.log_quantum(
                        g.name, p0, s0,
                        max_iters=quantum, k_local=4, compact=self.compact,
                        commits=res.stats.commits, epochs=res.stats.epochs,
                    )
                    self._writes_since_snapshot += 1
                    if self._writes_since_snapshot >= self.ft.snapshot_every:
                        store.snapshot(res.arena, seq)
                        self._writes_since_snapshot = 0
                    if rep is not None:
                        # ship the quantum's *inputs* to the hot standby: both
                        # copies apply the same serialized commit stream, so the
                        # replica is bit-identical to the primary by construction
                        rep.apply_quantum(
                            g.spec.iterator, p0, s0,
                            max_iters=quantum, k_local=4, compact=self.compact,
                        )
                        if self.ft.replication.verify_every_quantum:
                            rep.verify(res.arena)
                        shipped = True
                return res, time.perf_counter() - t0, fanned_out, shipped

        def apply(out):
            res, dt_s, fanned_out, shipped = out
            self.metrics.failover_quanta += int(fanned_out)
            self.metrics.replica_quanta += int(shipped)
            self._apply_result(g, occ, res, dt_s, rnd)

        return QuantumWork(label=g.name, run=run, apply=apply)

    # --------------------------- fault tolerance ------------------------------

    def _verify_recovery(self, recovered) -> None:
        """The zero-acknowledged-commits-lost gate: the snapshot + replayed
        log must reproduce the engine's resident arena exactly.  The engine
        swaps its arena only after a quantum succeeds, and a successful
        write quantum is logged before it is acknowledged, so any mismatch
        means durable state lost an acked commit -- fail loudly."""
        cur = self.engine.arena
        for field in ("data", "bounds", "perms", "heap"):
            if not np.array_equal(
                np.asarray(getattr(cur, field)), np.asarray(getattr(recovered, field))
            ):
                raise RuntimeError(
                    f"recovery lost acknowledged commits: arena.{field} diverged"
                )

    def _register_retry(self, g: _SlotGroup, rnd: int) -> None:
        """Park the failed group under jittered exponential backoff and
        charge each occupant one retry; budget exhaustion retires the
        request STATUS_RETRY (the client must resubmit after recovery)."""
        ft = self.ft
        m = self.metrics
        g.fail_streak += 1
        backoff = min(ft.backoff_cap, ft.backoff_base * (1 << (g.fail_streak - 1)))
        jitter = 1.0 + ft.backoff_jitter * (2.0 * self._ft_rng.random() - 1.0)
        g.backoff_until = rnd + 1 + max(1, int(round(backoff * jitter)))
        now_s = time.perf_counter()
        for s, r in enumerate(g.req):
            if r is None:
                continue
            r.retries += 1
            m.retries += 1
            if r.retries > ft.retry_budget:
                self._fast_retire(g, s, STATUS_RETRY, now_s, rnd)

    def _on_shard_failure(self, e: ShardFailure, rnd: int) -> None:
        """Fail over: mark the shard dead, restore the latest snapshot +
        replay the commit log, verify bit-equality with the resident arena,
        and park the in-flight group for a backed-off retry.  Runs on the
        main thread; in async mode the runner is already fail-fast idle
        (its error surfaced here), so swapping the arena is race-free."""
        m = self.metrics
        self._detector.suspect(e.shard, rnd)
        self._detector.sweep()
        t0 = time.perf_counter()
        recovered, info = self.ft.store.recover()
        self._verify_recovery(recovered)
        self.engine.arena = recovered
        m.recoveries += 1
        m.replayed_commits += info.replayed_commits
        m.recovery_ms_total += (time.perf_counter() - t0) * 1e3
        self._dead_until[e.shard] = rnd + 1 + self.ft.dead_rounds
        g = self.groups.get(e.label) if e.label else None
        if g is None:
            return
        if not g.spec.writes and self._has_live_replica(e.shard):
            # hot-standby fan-out: the failed call mutated nothing, so the
            # group's slot state is intact and simply re-runs next round --
            # now redirected to the replica.  Read-only tenants ride through
            # the death with zero STATUS_RETRY and zero backoff while the
            # snapshot+log recovery above rebuilds the primary.
            return
        self._register_retry(g, rnd)

    def _has_live_replica(self, shard: int) -> bool:
        """True when ``shard``'s range can be served from a replica holder
        that is itself alive (policy "primary" never redirects)."""
        if self._replicas is None or self._replicas.plan.policy == "primary":
            return False
        rm = self._replicas.plan.replica_map
        if not 0 <= shard < len(rm):
            return False
        holder = int(rm[shard])
        return holder >= 0 and holder not in self._detector.dead_shards()

    def _replica_ctx(self) -> routing.ReplicaContext | None:
        """Read fan-out operands for this quantum.  None when replication is
        off or nothing would redirect -- failover policy with every primary
        alive keeps the fast compiled schedule; "spread" always fans out."""
        if self._replicas is None:
            return None
        P = self.engine.arena.num_shards
        rm = self._replicas.plan.replica_map
        down = {s for s in self._detector.dead_shards() if 0 <= s < P}
        dead = np.zeros(P, bool)
        for s in down:
            # only fan out ranges whose holder is itself alive: marking a
            # primary dead with a dead holder leaves its range unservable
            # and the routed records would bounce forever.  A suspected
            # (slow) shard with a slow holder keeps serving its own range.
            holder = int(rm[s]) if s < len(rm) else -1
            if holder >= 0 and holder not in down:
                dead[s] = True
        if not dead.any() and self._replicas.plan.policy != "spread":
            return None
        return routing.ReplicaContext(
            plan=self._replicas.plan,
            rep_rows=self._replicas.rep_rows(),
            dead_mask=dead,
        )

    def _probe_shard(self, shard: int, *, warm: bool = False) -> float:
        """Time one single-record read against ``shard`` through the real
        dispatched superstep path.  ``warm=True`` compiles/warms only (no
        fault injection, no failure handling), so service init does not eat
        injected delays.  Live probes share the engine's fault-injector call
        stream: ``kill_call`` indices count probe calls too."""
        bounds = np.asarray(self.engine.arena.bounds)
        if bounds[shard + 1] - bounds[shard] <= 0:
            return 0.0  # empty range: nothing to probe
        ptr0 = np.array([int(bounds[shard])], np.int32)
        scr0 = np.zeros((1, 1), np.int32)
        t0 = time.perf_counter()
        try:
            routing.distributed_execute(
                _PROBE_IT, self.engine.arena, ptr0, scr0,
                mesh=self.engine.mesh, axis_name=self.engine.axis_name,
                max_iters=2, k_local=1, compact=True, schedule="dispatched",
                fault_injector=None if warm else self.engine.fault_injector,
            )
        except ShardFailure as e:
            if e.label is None:
                e.label = "watchdog"
            self._on_shard_failure(e, max(self._wd_round, 0))
            return float("inf")
        return time.perf_counter() - t0

    def _run_watchdog(self, rnd: int) -> None:
        """Per-round shard watchdog: probe every live shard, beat the ones
        that answered within ``ft.watchdog_timeout_s``, and escalate missed
        beats to suspected-dead.  This catches *stragglers* (delay faults)
        that stall supersteps without ever raising ShardFailure: the next
        round's reads fan out to the replica instead of waiting."""
        m = self.metrics
        if self._wd_round < 0:
            # first round (or just resharded): baseline every shard as if
            # beaten last round, so the two-consecutive-misses confirmation
            # window starts counting from here
            self._wd_round = rnd - 1
            for s in self._watchdog.hosts:
                self._watchdog.beat(s)
        self._wd_round = rnd
        dead_now = set(self._detector.dead_shards())
        for s in range(self.engine.arena.num_shards):
            if s in dead_now:
                continue  # already degraded; do not stall on a dead shard
            dt = self._probe_shard(s)
            m.watchdog_probes += 1
            if dt <= self.ft.watchdog_timeout_s:
                self._watchdog.beat(s)
        for s in self._watchdog.sweep():
            if s in dead_now or s in self._detector.dead_shards():
                continue
            m.watchdog_suspects += 1
            self._detector.suspect(s, rnd)
            self._detector.sweep()
            self._dead_until[s] = rnd + 1 + self.ft.dead_rounds

    def _revive_dead_shards(self, rnd: int) -> None:
        for k in [k for k, until in self._dead_until.items() if until <= rnd]:
            self._detector.revive(k)
            if self._watchdog is not None and k in self._watchdog.hosts:
                # re-arm the watchdog beat so a still-slow revived shard is
                # re-suspected (sweep only reports *newly* missed beats)
                self._watchdog.beat(k)
            del self._dead_until[k]

    # ------------------------------ elasticity --------------------------------

    def request_reshard(self, new_num_shards: int) -> None:
        """Begin an online 2x shard-count change.  Admission pauses, every
        in-flight quantum drains through the existing write-barrier
        machinery, then the arena cuts over (``arena.remap_shards`` +
        owner-epoch forwarding + mesh rebuild) and admission resumes.  The
        result is bit-identical to a cold rebuild at the new shard count:
        the remap is deterministic and nothing routes during the swap."""
        self._reshard.request(
            int(new_num_shards),
            current=self.engine.arena.num_shards,
            rnd=self.metrics.rounds,
        )

    def _in_flight(self) -> int:
        return sum(int(g.occupied().sum()) for g in self.groups.values())

    def _cutover(self, rnd: int) -> None:
        m = self.metrics
        old_p = self.engine.arena.num_shards
        target = self._reshard.target
        new_arena = remap_shards(self.engine.arena, target)
        new_mesh = None
        if self.engine.mesh is not None:
            new_mesh = mem_mesh(target, axis_name=self.engine.axis_name)
        ep = self._owner_map.advance(np.asarray(new_arena.bounds))
        old_epoch = ep.epoch - 1

        def fwd(s: int) -> tuple[int, ...]:
            return self._owner_map.forward_shard(
                s, from_epoch=old_epoch, to_epoch=ep.epoch
            )

        # stale per-shard serving state (minted under the old owner
        # function) forwards through the new epoch: a shard index never
        # survives a reshard raw, only via range translation
        self._dead_until = {
            d: until for s, until in self._dead_until.items() for d in fwd(s)
        }
        if self._detector is not None:
            from repro.distributed.elastic import ShardFailureDetector

            old_dead = self._detector.dead_shards()
            self._detector = ShardFailureDetector(target)
            for s in old_dead:
                for d in fwd(s):
                    self._detector.suspect(d, rnd)
            self._detector.sweep()
        self.engine.reshard(new_arena, new_mesh)
        if self._replicas is not None:
            repc = self.ft.replication
            prim = repc.primaries
            if prim is not None:
                prim = tuple(sorted({d for p in prim for d in fwd(p)}))
            plan = routing.make_replica_plan(target, prim, policy=repc.policy)
            # the standby reshards through the same deterministic remap, so
            # primary and replica stay bit-identical across the cutover
            self._replicas.reset(
                remap_shards(self._replicas.shadow, target), plan
            )
        if self._watchdog is not None:
            from repro.distributed.elastic import HeartbeatMonitor

            self._watchdog = HeartbeatMonitor(
                target, timeout_s=1, clock=lambda: self._wd_round
            )
            self._wd_round = -1  # re-arm the confirmation baseline
        if self.ft is not None:
            # a marker + snapshot land in the log so recovery replay never
            # straddles two partitions
            store = self.ft.store
            seq = store.log.append(
                {
                    "kind": "reshard",
                    "old_shards": old_p,
                    "new_shards": target,
                    "owner_epoch": ep.epoch,
                }
            )
            store.snapshot(self.engine.arena, seq)
            self._writes_since_snapshot = 0
        ev = self._reshard.complete(rnd=rnd, old_shards=old_p, owner_epoch=ep.epoch)
        m.reshards += 1
        m.reshard_drain_rounds += ev.drain_rounds

    def _quantum_for_round(self, now_s: float) -> int:
        """SLO-aware quantum sizing.  With the bounds pinned (the default)
        this returns the fixed ``quantum``.  Otherwise: no deadline in
        sight -> grow multiplicatively toward ``max_quantum`` (fewer
        rounds, fewer dispatches per request); a deadline pending or on
        device -> fit the quantum inside the earliest deadline's headroom
        using the EWMA ms/iter estimate, floored at ``min_quantum`` so
        forward progress never stalls."""
        lo, hi = self.min_quantum, self.max_quantum
        if lo == hi:
            return lo
        deadlines = []
        q_dl = self.admission.earliest_deadline_s()
        if q_dl is not None:
            deadlines.append(q_dl)
        for g in self.groups.values():
            for r in g.req:
                if r is not None and r.deadline_ms is not None:
                    deadlines.append(r.arrival_s + r.deadline_ms / 1e3)
        if not deadlines or self._ms_per_iter is None:
            self._cur_quantum = min(hi, max(lo, self._cur_quantum * 2))
        else:
            headroom_ms = max(0.0, (min(deadlines) - now_s) * 1e3)
            target = int(headroom_ms * self.slo_safety / self._ms_per_iter)
            self._cur_quantum = min(hi, max(lo, target))
        return self._cur_quantum

    def _ensure_runner(self) -> DeviceRunner | None:
        if self.pipeline != "async":
            return None
        if self._runner is None:
            self._runner = DeviceRunner(depth=self.runner_depth).start()
        return self._runner

    def _busy(self) -> bool:
        return (
            bool(self._pending_arrivals)
            or self.admission.pending() > 0
            or any(g.occupied().any() for g in self.groups.values())
            or self._reshard.phase != "idle"
        )

    def step(self, rnd: int | None = None) -> None:
        """One scheduling round: admit -> run every occupied group -> retire.

        sync: each group's quantum executes inline, retirement accounting
        drains at the end of the round.  async: group quanta are handed to
        the DeviceRunner (bounded double-buffered queue) and this thread
        books prior retirements while the device chews; the round ends on
        the runner's drain barrier, so the next round's admission sees
        settled slot state and the engine-call sequence matches sync
        exactly."""
        m = self.metrics
        rnd = m.rounds if rnd is None else rnd
        with TRACE.span("service.round", round=rnd):
            now = time.perf_counter()
            if self._detector is not None:
                self._revive_dead_shards(rnd)
            if self._reshard.phase == "draining":
                # reshard barrier: arrivals keep queueing, nothing admits, and
                # the cutover fires the round the last in-flight quantum retires
                self._intake(now, rnd)
                if self._reshard.should_cutover(self._in_flight()):
                    self._cutover(rnd)
                    self._admit(now, rnd)
            else:
                self._admit(now, rnd)
            quantum = self._quantum_for_round(now)
            if m.quantum_min_used == 0 or quantum < m.quantum_min_used:
                m.quantum_min_used = quantum
            m.quantum_max_used = max(m.quantum_max_used, quantum)
            runner = self._ensure_runner()
            for g in self.groups.values():
                if not g.occupied().any() or g.backoff_until > rnd:
                    continue  # empty, or parked awaiting a backed-off retry
                work = self._make_work(g, rnd, quantum)
                try:
                    if runner is not None:
                        # a pending runner error surfaces here *before* work
                        # enqueues: the current group simply re-runs next round
                        runner.submit(work)
                    else:
                        work.apply(work.run())
                except ShardFailure as e:
                    if self.ft is None:
                        raise
                    if e.label is None:
                        e.label = g.name
                    self._on_shard_failure(e, rnd)
            if runner is not None:
                self._drain_emit()  # overlap: account retirements mid-flight
                try:
                    runner.drain()  # barrier: slot state settled for next admit
                except ShardFailure as e:
                    if self.ft is None:
                        raise
                    self._on_shard_failure(e, rnd)
            self._drain_emit()
            if self._watchdog is not None:
                self._run_watchdog(rnd)
            if self._detector is not None:
                self._detector.beat_all(rnd)
            m.rounds += 1

    def close(self) -> None:
        """Stop the background runner (idempotent; restarted on demand)."""
        if self._runner is not None:
            self._runner.close()
            self._runner = None

    def run(
        self,
        requests: list[TraversalRequest] | None = None,
        *,
        max_rounds: int = 100_000,
    ) -> ServiceMetrics:
        """Serve until every submitted request has retired."""
        t0 = time.perf_counter()
        for r in requests or []:
            self.submit(r)
        try:
            while self._busy():
                if self.metrics.rounds >= max_rounds:
                    raise RuntimeError(
                        f"service did not drain in {max_rounds} rounds"
                    )
                self.step()
        finally:
            self.close()
            self._drain_emit()
        self.metrics.wall_s += time.perf_counter() - t0
        return self.metrics
