"""PulseEngine: the user-facing traversal engine (dispatch + execute).

Execution paths (the compared systems of S6):
  * ``local``        -- single memory node, PULSE accelerator semantics
                        (``iterator.execute_batched``).
  * ``distributed``  -- multi-node with in-network switch routing (S5).
  * ``distributed`` + ``return_to_cpu=True`` -- the PULSE-ACC ablation
                        (Fig. 9): crossings bounce through the home node.
  * ``cpu_node``     -- the Cache-based baseline: the traversal runs at the
                        CPU node; every node fetch is a remote access unless
                        it hits the CPU-side cache (LRU, S2.1).  Functionally
                        identical results + an access trace for the latency /
                        energy models.

The dispatch engine's offload decision (t_c <= eta * t_d, S4.1) lives in
``core.dispatch``; ``PulseEngine.execute`` consults it and falls back to the
``cpu_node`` path for non-offloadable iterators, exactly like the paper.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch as dispatch_mod
from repro.core import routing
from repro.core.arena import NULL, Arena
from repro.core.iterator import (
    STATUS_ACTIVE,
    STATUS_DONE,
    STATUS_FAULT,
    STATUS_MAXED,
    PulseIterator,
    execute_batched,
)

# Re-exported: the specialization predicate lives with the distributed
# executor but is part of the engine's public surface (callers asking "will
# this run probe-free?" shouldn't need to know which layer owns the proof).
can_elide_access_check = routing.can_elide_access_check


@dataclasses.dataclass
class CpuNodeTrace:
    """Access trace from the cpu_node path (feeds Fig. 7 latency models)."""

    total_fetches: int
    cache_hits: int
    per_request_iters: np.ndarray

    @property
    def misses(self) -> int:
        return self.total_fetches - self.cache_hits


def cpu_node_execute(
    it: PulseIterator,
    arena: Arena,
    ptr0,
    scratch0,
    *,
    max_iters: int = 1 << 20,
    cache_nodes: int = 0,
):
    """Cache-based baseline: traverse at the CPU node over remote memory.

    Functionally equivalent to the accelerator path; additionally simulates a
    CPU-side LRU cache of ``cache_nodes`` node records and reports the trace.
    Runs hop-by-hop on host (numpy) -- it *is* the slow path being modeled.
    """
    data = np.asarray(arena.data)
    ptr = np.asarray(ptr0, np.int64).copy()
    B = ptr.shape[0]
    scratch = np.asarray(scratch0, np.int32).reshape(B, it.scratch_words).copy()
    done = np.zeros(B, bool)
    iters = np.zeros(B, np.int64)
    lru: OrderedDict[int, None] = OrderedDict()
    hits = fetches = 0

    step = jax.jit(jax.vmap(lambda n, p, s: _fused_step(it, n, p, s)))
    while not done.all() and (iters[~done].min(initial=0) < max_iters):
        live = ~done & (ptr != NULL)
        if not live.any():
            break
        # CPU-node cache simulation, per node fetch
        for a in ptr[live]:
            fetches += 1
            a = int(a)
            if a in lru:
                hits += 1
                lru.move_to_end(a)
            elif cache_nodes > 0:
                lru[a] = None
                if len(lru) > cache_nodes:
                    lru.popitem(last=False)
        node = data[np.clip(ptr, 0, data.shape[0] - 1)]
        d, np_, ns = step(jnp.asarray(node), jnp.asarray(ptr, jnp.int32), jnp.asarray(scratch))
        d, np_, ns = np.asarray(d), np.asarray(np_), np.asarray(ns)
        scratch[live] = ns[live]
        iters[live] += 1
        newly_done = live & (d | (np_ == NULL) | (iters >= max_iters))
        ptr[live & ~newly_done] = np_[live & ~newly_done]
        done |= newly_done
    trace = CpuNodeTrace(fetches, hits, iters.copy())
    return ptr.astype(np.int32), scratch, iters, trace


def _fused_step(it: PulseIterator, node, ptr, scratch):
    if it.step_fn is not None:
        return it.step_fn(node, ptr, scratch)
    done, scr = it.end_fn(node, ptr, scratch)
    nptr, nscr = it.next_fn(node, ptr, scr)
    return done, jnp.where(done, ptr, nptr), jnp.where(done, scr, nscr)


@dataclasses.dataclass
class ExecResult:
    ptr: np.ndarray
    scratch: np.ndarray
    status: np.ndarray
    iters: np.ndarray
    stats: object | None = None
    offloaded: bool = True
    # write path: the post-commit arena (mutating iterators only).  The
    # engine already swapped its own resident arena to this value; callers
    # holding the pre-call Arena object keep an intact snapshot.
    arena: Arena | None = None
    # where the batch ran: "xla" (single-node device executor), "kernel"
    # (pulse_chase), "distributed" (mesh supersteps), "cpu_node" (host
    # traversal, offloaded=False), "commit_oracle" (one-shard writes on the
    # host's sequential-commit executor) or "distributed_commit"
    path: str = "xla"


class PulseEngine:
    """Front door: dispatch decision + the right execution path."""

    def __init__(
        self,
        arena: Arena,
        *,
        mesh=None,
        axis_name: str = "mem",
        accel: dispatch_mod.AcceleratorSpec | None = None,
        eta: float | None = None,
        fault_injector=None,
    ):
        self.arena = arena
        self.mesh = mesh
        self.axis_name = axis_name
        self.accel = accel or dispatch_mod.AcceleratorSpec()
        self.eta = self.accel.eta if eta is None else eta
        # test-only fault hook (core.faults.FaultInjector); every execute()
        # counts as one call toward the plan's kill_call regardless of path
        self.fault_injector = fault_injector
        # serving calls execute() every scheduling round with a fixed batch
        # shape; cache the compiled local executor per (iterator, B, budget).
        # The kernel path's logic closure is cached per iterator in
        # routing._kernel_logic (pulse_chase jits on logic_fn identity, so a
        # fresh closure per call would retrace) -- one cache shared by the
        # single-node kernel path and the distributed local_backend="kernel".
        self._local_jit: dict = {}
        # schedule_decision re-traces the iterator's jaxpr for the overlap
        # model; serving calls execute() per quantum, so cache per iterator
        self._schedule_cache: dict = {}

    def _local_fault_check(self):
        """Fault accounting for execution paths that never enter the
        distributed/commit executors (local jit, kernel, cpu_node): register
        the engine call and fire the kill before any work runs.  The leaf
        executors own their begin_call, so this must NOT run for paths that
        delegate to them (double-counting would skew kill_call targeting)."""
        inj = self.fault_injector
        if inj is not None:
            k = inj.kill_step(inj.begin_call())
            if k is not None:
                inj.fire(k)

    def dispatch(self, it: PulseIterator) -> dispatch_mod.OffloadDecision:
        return dispatch_mod.offload_decision(
            it, self.arena.node_words, self.accel, eta=self.eta
        )

    def reshard(self, arena: Arena, mesh=None) -> None:
        """Install a re-partitioned arena (and optionally a new mesh width).

        Shard-count-dependent decision caches are dropped; compiled
        executables key on shapes/static args and stay valid for whatever
        still matches."""
        self.arena = arena
        if mesh is not None:
            self.mesh = mesh
        self._schedule_cache.clear()

    def execute(
        self,
        it: PulseIterator,
        ptr0,
        scratch0,
        *,
        max_iters: int = 1 << 20,
        force_offload: bool | None = None,
        return_to_cpu: bool = False,
        k_local: int = 4,
        cache_nodes: int = 0,
        compact: bool = True,
        fused: bool = True,
        backend: str = "xla",
        schedule: str = "auto",
        fabric: str = "dense",
        replication: routing.ReplicaContext | None = None,
    ) -> ExecResult:
        """Dispatch + execute a batch of traversals.

        ``backend`` selects the single-node executor: ``"xla"`` is the pure
        JAX while_loop oracle; ``"kernel"`` runs the pulse_chase Pallas
        kernel under the variable-depth wave scheduler (compiled on TPU, the
        Pallas interpreter on the CPU backend; any other backend raises),
        retiring finished lanes between depth quanta.  On a mesh,
        ``backend="kernel"`` threads the distributed local chase through
        the kernel's vectorized iterator body
        (``local_backend="kernel"``), so the overlapped local step shares
        the accelerator's compiled logic end-to-end.

        ``schedule`` picks the distributed superstep engine: ``"auto"``
        consults the dispatch engine's overlap model
        (``dispatch.schedule_decision``) and normally selects the
        wavefront-pipelined loop, which overlaps the in-flight wavefront's
        fabric time with the resident wavefront's local chase; ``"fused"``
        and ``"dispatched"`` force the serialized schedules.  ``fabric``
        selects the collective carrying the records (dense all_to_all or a
        ppermute ring).  All combinations are bit-identical in results and
        wire accounting.  ``compact`` enables active-set compaction of
        distributed supersteps (ignored for the ``return_to_cpu`` ablation);
        ``fused`` is the pre-pipelined boolean knob, still honored when
        ``schedule="auto"`` resolves away from it only by the overlap model.
        """
        if it.mutates:
            # write iterators always run near-memory: the commit machinery
            # (per-shard serialization, free-list allocator) lives with the
            # data, so there is no CPU-node fallback to dispatch them to --
            # and the knobs that would bypass it are errors, not no-ops
            if return_to_cpu:
                raise ValueError(
                    "mutating iterators cannot run the return_to_cpu ablation"
                )
            if backend == "kernel":
                raise ValueError(
                    "mutating iterators are not supported on the pulse_chase "
                    "kernel backend yet; use backend='xla'"
                )
            if force_offload is False:
                raise ValueError(
                    "mutating iterators cannot run at the CPU node "
                    "(force_offload=False): commits live with the data"
                )
            return self._execute_mut(
                it, ptr0, scratch0, max_iters=max_iters, k_local=k_local,
                compact=compact, fused=fused, schedule=schedule, fabric=fabric,
            )
        decision = self.dispatch(it)
        offload = decision.offload if force_offload is None else force_offload
        if not offload:
            self._local_fault_check()
            ptr, scratch, iters, trace = cpu_node_execute(
                it, self.arena, ptr0, scratch0,
                max_iters=max_iters, cache_nodes=cache_nodes,
            )
            status = np.where(iters >= max_iters, 2, STATUS_DONE).astype(np.int32)
            return ExecResult(
                ptr, scratch, status, np.asarray(iters), trace, False,
                path="cpu_node",
            )

        if self.mesh is not None and self.arena.num_shards > 1:
            if replication is not None:
                # replica fan-out runs on the dispatched schedule; results
                # are schedule-invariant, so degraded/spread rounds just use
                # the host loop instead of the overlap model's pick
                schedule = "dispatched"
            else:
                schedule = self._resolve_schedule(it, schedule, fused, k_local)
            rec, stats = routing.distributed_execute(
                it, self.arena, ptr0, scratch0,
                mesh=self.mesh, axis_name=self.axis_name,
                max_iters=max_iters, k_local=k_local,
                return_to_cpu=return_to_cpu, compact=compact, fused=fused,
                schedule=schedule, fabric=fabric,
                local_backend="kernel" if backend == "kernel" else "xla",
                fault_injector=self.fault_injector,
                replication=replication,
            )
            return ExecResult(
                ptr=rec[:, routing.F_PTR],
                scratch=rec[:, routing.F_SCRATCH:],
                status=rec[:, routing.F_STATUS],
                iters=rec[:, routing.F_ITERS],
                stats=stats,
                path="distributed",
            )

        if backend == "kernel":
            self._local_fault_check()
            return self._execute_kernel(it, ptr0, scratch0, max_iters=max_iters)

        self._local_fault_check()
        # jnp.array copies (unlike asarray), so donating the copies keeps the
        # caller's buffers alive while letting the while_loop alias in place.
        # The iteration budget is a traced operand (not part of the key), so
        # SLO-aware quantum sizing in the serving layer re-enters the same
        # compiled executable with a different budget every round.
        # Re-derive the access-check elision per call (perms can change
        # between calls) and key the cache on it: a revocation flips the key
        # back to the unspecialized executable instead of silently running
        # the probe-free one.
        elide = routing.can_elide_access_check(it, self.arena)
        ptr0 = jnp.array(ptr0, jnp.int32)
        key = (it, int(ptr0.shape[0]), elide)
        fn = self._local_jit.get(key)
        if fn is None:
            fn = jax.jit(
                lambda arena, p, s, budget: execute_batched(
                    it, arena, p, s, max_iters=budget, elide_access_check=elide
                ),
                donate_argnums=(1, 2),
            )
            self._local_jit[key] = fn
        ptr, scratch, status, iters = fn(
            self.arena, ptr0, jnp.array(scratch0, jnp.int32),
            jnp.int32(min(max_iters, (1 << 31) - 1)),
        )
        return ExecResult(
            np.asarray(ptr), np.asarray(scratch), np.asarray(status),
            np.asarray(iters),
        )

    def _resolve_schedule(
        self, it: PulseIterator, schedule: str, fused: bool, k_local: int
    ) -> str:
        """``schedule="auto"`` -> the dispatch engine's overlap-model pick
        (cached per iterator); ``fused=False`` is the explicit opt-out of
        device-resident loops.  Shared by the read and write paths."""
        if schedule != "auto":
            return schedule
        if not fused:
            return "dispatched"
        sk = (it, k_local)
        sd = self._schedule_cache.get(sk)
        if sd is None:
            sd = self._schedule_cache[sk] = dispatch_mod.schedule_decision(
                it, self.arena.node_words, self.arena.num_shards,
                self.accel, k_local=k_local,
            )
        return sd.schedule if sd.schedule != "local" else "fused"

    def _execute_mut(
        self,
        it: PulseIterator,
        ptr0,
        scratch0,
        *,
        max_iters: int,
        k_local: int,
        compact: bool,
        fused: bool,
        schedule: str,
        fabric: str,
    ) -> ExecResult:
        """Write path: run a mutating iterator and swap the engine's arena to
        the post-commit state.

        The distributed path threads the arena + heap registers through the
        superstep loops as carried state; single-node (no mesh / one shard)
        runs the sequential-commit executor (``core.commit``) -- the same
        semantics the distributed schedules are verified against bit-for-bit.
        The *input* arena object is never modified, so callers can replay a
        snapshot through several schedules.
        """
        S = it.scratch_words
        if self.mesh is not None and self.arena.num_shards > 1:
            schedule = self._resolve_schedule(it, schedule, fused, k_local)
            rec, stats, new_arena = routing.distributed_execute(
                it, self.arena, ptr0, scratch0,
                mesh=self.mesh, axis_name=self.axis_name,
                max_iters=max_iters, k_local=k_local,
                compact=compact, schedule=schedule, fabric=fabric,
                fault_injector=self.fault_injector,
            )
            path = "distributed_commit"
        else:
            from repro.core import commit as commit_mod

            rec, stats, new_arena = commit_mod.sequential_commit_execute(
                it, self.arena, ptr0, scratch0,
                max_iters=max_iters, k_local=k_local, compact=compact,
                fault_injector=self.fault_injector,
            )
            path = "commit_oracle"
        self.arena = new_arena
        return ExecResult(
            ptr=rec[:, routing.F_PTR],
            scratch=rec[:, routing.F_SCRATCH : routing.F_SCRATCH + S],
            status=rec[:, routing.F_STATUS],
            iters=rec[:, routing.F_ITERS],
            stats=stats,
            arena=new_arena,
            path=path,
        )

    def _execute_kernel(
        self, it: PulseIterator, ptr0, scratch0, *, max_iters: int
    ) -> ExecResult:
        """Single-node path on the pulse_chase kernel (variable-depth waves).

        Translation/protection faults (NULL or out-of-range pointers,
        perm-revoked ranges) are enforced by a host-side ``fault_fn`` between
        depth quanta, so detection is quantum-granular rather than
        per-iteration like the XLA executor -- a faulting lane may execute a
        few extra clamped (harmless) loads first.  Lanes still active after
        ``max_iters`` report MAXED (resumable).  Iteration counts are exact
        per lane (the kernel accumulates them; wave retirement no longer
        rounds up to the depth quantum), except for fault_fn-retired lanes,
        whose counts include the clamped loads executed before the
        quantum-granular check caught them.  Runs the compiled kernel on TPU
        and the Pallas interpreter on CPU; any other backend is an error.
        """
        from repro.core.arena import PERM_READ
        from repro.kernels.pulse_chase import ops as chase_ops

        ptr0 = np.asarray(ptr0, np.int32)
        B = ptr0.shape[0]
        scratch0 = np.asarray(scratch0, np.int32).reshape(B, it.scratch_words)
        logic = routing._kernel_logic(it)
        max_steps = int(min(max_iters, 1 << 20))

        bounds = np.asarray(self.arena.bounds)
        perms = np.asarray(self.arena.perms)
        cap = self.arena.capacity

        def fault_fn(p):
            shard = np.searchsorted(bounds, p, side="right") - 1
            ok = perms[np.clip(shard, 0, perms.shape[0] - 1)] & PERM_READ
            return (p < 0) | (p >= cap) | (ok != PERM_READ)

        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"the pulse_chase kernel compiles for TPU only (and runs in "
                f"the Pallas interpreter on CPU); backend is {platform!r}"
            )
        ptr, scratch, st, wstats = chase_ops.pulse_chase_waves(
            self.arena.data, ptr0, scratch0, np.zeros(B, np.int32),
            logic_fn=logic, max_steps=max_steps, fault_fn=fault_fn,
            interpret=platform == "cpu",
        )
        status = np.where(st == 1, STATUS_DONE, STATUS_MAXED).astype(np.int32)
        status = np.where(wstats.faulted, STATUS_FAULT, status)
        return ExecResult(
            ptr, scratch, status, wstats.retire_step.astype(np.int32), wstats,
            path="kernel",
        )
