"""Distributed pointer traversals: the in-network switch as supersteps (S5).

The paper routes in-flight traversal requests between memory nodes with a
programmable switch that holds only the range-partition base table.  On a TPU
mesh the ICI collectives *are* the programmable fabric, so we route **batches**
of fixed-size request records with ``all_to_all`` in bulk-synchronous
supersteps.  The paper's key properties are preserved exactly:

  * a cross-node hop never bounces through the CPU node (compare
    ``return_to_cpu=True``, the paper's PULSE-ACC ablation, Fig. 9);
  * the request and the response share one wire format, so any shard can
    continue any traversal it receives (S5 "continuing stateful iterator
    execution");
  * the switch knows only ``bounds`` (hierarchical translation, Fig. 6);
    per-shard translation/protection happens at the owning shard.

Record wire format (R = 6 + S [+ 4 + W] int32 words):
  [id, home_shard, cur_ptr, status, iters, hops, scratch_pad...,
   m_op, m_tgt, m_mask, m_expect, m_data...]

The trailing mutation payload exists only for *mutating* iterators (the
write path): a staged mutation rides the same all_to_all/ring fabric as the
traversal itself, routed to the shard that owns its commit target, where the
per-shard commit phase applies it (``_commit_phase``).  Read-only records
keep the original 6 + S layout, so the read path's wire accounting is
untouched.
"""

from __future__ import annotations

import dataclasses
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core import translation
from repro.core.arena import (
    H_BUMP,
    H_COMMITS,
    H_EPOCH,
    H_FREE,
    M_ALLOC,
    M_CAS,
    M_FREE,
    M_NONE,
    M_STORE,
    NULL,
    PERM_READ,
    PERM_WRITE,
    Arena,
    mut_width,
)
from repro.core.iterator import (
    STATUS_ACTIVE,
    STATUS_DONE,
    STATUS_EMPTY,
    STATUS_FAULT,
    STATUS_MAXED,
    PulseIterator,
    mut_step_batch,
    step_batch,
)
from repro.core.trace import TRACE

F_ID, F_HOME, F_PTR, F_STATUS, F_ITERS, F_HOPS, F_SCRATCH = 0, 1, 2, 3, 4, 5, 6


def record_width(scratch_words: int, mut_words: int = 0) -> int:
    return F_SCRATCH + scratch_words + mut_words


@dataclasses.dataclass(frozen=True)
class ReplicaPlan:
    """Static hot-shard replication wiring (R=2) for the READ path.

    ``primary_map[r]`` names the primary shard whose rows replica-holder
    ``r`` mirrors (-1: r holds no replica); ``replica_map[p]`` is the
    inverse (-1: p is unreplicated).  Both are tuples so the plan is
    hashable and can key the compiled-superstep caches.

    ``policy`` is the read fan-out rule the switch applies per record:

      * ``"primary"``  -- never redirect (replicas are cold standbys);
      * ``"failover"`` -- redirect a read to the replica only while the
        primary is marked dead in the traced ``dead_mask``;
      * ``"spread"``   -- load-balance: odd request ids read from the
        replica, even ids from the primary (dead primaries always
        redirect).  Replicas are bit-identical by construction, so the
        copy that serves a read never changes its result.
    """

    primary_map: tuple
    replica_map: tuple
    policy: str = "failover"

    def __post_init__(self):
        if self.policy not in ("primary", "failover", "spread"):
            raise ValueError(f"unknown replica policy {self.policy!r}")
        if len(self.primary_map) != len(self.replica_map):
            raise ValueError("primary_map / replica_map length mismatch")

    @property
    def num_shards(self) -> int:
        return len(self.primary_map)

    @property
    def replicated(self) -> tuple:
        """Primaries that have a live replica."""
        return tuple(p for p, r in enumerate(self.replica_map) if r >= 0)


def make_replica_plan(
    num_shards: int, primaries=None, *, policy: str = "failover"
) -> ReplicaPlan:
    """Build an R=2 plan: primary ``p``'s rows are mirrored on shard
    ``(p + num_shards // 2) % num_shards`` (the antipode -- a correlated
    rack failure of neighbours never takes both copies).  ``primaries``
    defaults to every shard; each holder mirrors at most one primary."""
    if primaries is None:
        primaries = range(num_shards)
    primary_map = [-1] * num_shards
    replica_map = [-1] * num_shards
    for p in primaries:
        r = (p + max(1, num_shards // 2)) % num_shards
        if primary_map[r] != -1:
            raise ValueError(
                f"replica holder {r} already mirrors shard {primary_map[r]}"
            )
        primary_map[r] = int(p)
        replica_map[p] = int(r)
    return ReplicaPlan(tuple(primary_map), tuple(replica_map), policy)


@dataclasses.dataclass
class ReplicaContext:
    """Per-call replication operands for ``distributed_execute``.

    ``rep_rows`` mirrors the arena-data layout ``(capacity, node_words)``
    sharded over the mesh axis: replica-holder ``r``'s slice is a copy of
    ``primary_map[r]``'s rows (zeros when r holds none) -- each shard
    stores at most one extra shard's rows, the R=2 memory budget.
    ``dead_mask`` is the traced per-call failure-detector verdict, so the
    same compiled superstep serves healthy and degraded rounds.
    """

    plan: ReplicaPlan
    rep_rows: object  # (capacity, node_words) int32, holder-sharded
    dead_mask: object  # (P,) bool


def _serve_shard(owner, rec_id, rep_ctx):
    """The switch's serve map: which shard answers a read at ``owner``'s
    range under the fan-out policy.  Identity when replication is off."""
    if rep_ctx is None:
        return owner
    replica_arr, dead_mask, policy = rep_ctx
    num = replica_arr.shape[0]
    safe = jnp.clip(owner, 0, num - 1)
    alt = replica_arr[safe]
    # a dead replica holder is no fallback: its copy died with it
    has_alt = (alt >= 0) & (owner >= 0) & ~dead_mask[jnp.clip(alt, 0, num - 1)]
    dead = dead_mask[safe]
    if policy == "spread":
        redirect = has_alt & (dead | ((rec_id % 2) == 1))
    elif policy == "failover":
        redirect = has_alt & dead
    else:  # "primary"
        redirect = jnp.zeros_like(has_alt)
    return jnp.where(redirect, alt, owner).astype(jnp.int32)


def pack_requests(ids, home, ptr, scratch, mut_words: int = 0) -> jnp.ndarray:
    B, S = scratch.shape
    rec = jnp.zeros((B, record_width(S, mut_words)), jnp.int32)
    rec = rec.at[:, F_ID].set(ids)
    rec = rec.at[:, F_HOME].set(home)
    rec = rec.at[:, F_PTR].set(ptr)
    rec = rec.at[:, F_STATUS].set(STATUS_ACTIVE)
    return rec.at[:, F_SCRATCH : F_SCRATCH + S].set(scratch)


def empty_records(n: int, scratch_words: int) -> jnp.ndarray:
    rec = jnp.zeros((n, record_width(scratch_words)), jnp.int32)
    return rec.at[:, F_STATUS].set(STATUS_EMPTY)


@dataclasses.dataclass
class RoutingStats:
    supersteps: int
    crossings: np.ndarray  # (B,) network crossings per request (Fig. 2c/9)
    routed_per_step: list  # valid records exchanged per superstep
    active_per_step: list = dataclasses.field(default_factory=list)
    wire_words_per_step: list = dataclasses.field(default_factory=list)
    # int32 words shipped across off-shard links per superstep (the BSP
    # all_to_all payload: num_shards * (num_shards-1) * link_capacity * R;
    # 0 for compacted local-only supersteps that skip the fabric entirely)
    capacity_per_step: list = dataclasses.field(default_factory=list)
    local_only_steps: int = 0  # supersteps that skipped the all_to_all
    # Fused executions stay device-resident for the whole loop, so the
    # per-step lists above are empty and only this aggregate (decoded from
    # traced counters after the while_loop exits) is available.  NOTE: wire
    # words are the *modeled* switch payload (the paper's BSP accounting at
    # the scheduled capacity rung) on both paths; physically, the dispatched
    # path compiles a buffer per rung while the fused path always exchanges
    # the static base-capacity buffer (shapes cannot be traced) -- fused
    # trades that physical shrinkage for zero per-hop host dispatch, and
    # only its local-only lax.cond skips remove real transfers.
    wire_words_total: int | None = None
    fused: bool = False
    # which superstep schedule produced this run ("dispatched" | "fused" |
    # "pipelined") and which fabric carried the records ("dense" all_to_all
    # | "ring" ppermute distance classes).  The pipelined schedule overlaps
    # the in-flight wavefront's fabric time with the resident wavefront's
    # local chase; scheduling decisions, wire accounting, and results are
    # bit-identical to the fused schedule.
    schedule: str = "dispatched"
    fabric: str = "dense"
    # write path: mutations applied by per-shard commit phases during this
    # execution (CAS misses included -- they consumed a serialized commit
    # slot), and commit epochs advanced (the per-shard lock-generation
    # counter; one per superstep that applied >= 1 mutation on some shard)
    commits: int = 0
    epochs: int = 0

    @property
    def total_wire_words(self) -> int:
        if self.wire_words_total is not None:
            return int(self.wire_words_total)
        return int(sum(self.wire_words_per_step))

    @property
    def ring_hops(self) -> int:
        """Physical ppermute hops a ring fabric executed (P-1 distance
        classes per routed superstep; 0 on the dense fabric)."""
        if self.fabric != "ring":
            return 0
        routed = self.supersteps - self.local_only_steps
        return routed * max(0, self._num_shards - 1) if self._num_shards else 0

    _num_shards: int = 0


@dataclasses.dataclass
class ExecutableCacheStats:
    """Counters for the compiled-superstep caches (regression-tested: serving
    quanta and repeated engine calls with same-shaped pools must not retrace).

    ``traces`` counts actual Python traces of the step/loop bodies (bumped
    from inside the traced function, so it only moves when XLA recompiles);
    ``hits``/``misses`` count executable-cache lookups.
    """

    hits: int = 0
    misses: int = 0
    traces: int = 0

    def reset(self) -> None:
        self.hits = self.misses = self.traces = 0


CACHE_STATS = ExecutableCacheStats()


# Kernel-backend iterator bodies: the vectorized fused next+end `logic_fn`
# compiled by kernels/pulse_chase (one entry per iterator; lazily imported to
# avoid a routing <-> kernels import cycle).  Threading the distributed local
# superstep through this shares the exact iterator body the accelerator
# kernel executes, so the overlapped local step is the kernel fast path
# end-to-end (engine backend="kernel" on a mesh).
_KERNEL_LOGIC: dict = {}


def _is_vm_backed(it: PulseIterator) -> bool:
    """True for iterators whose step/mut function is the ISA VM (carries the
    ``__wrapped_program__`` marker the dispatch cost model also keys on)."""
    return any(
        hasattr(fn, "__wrapped_program__") for fn in (it.step_fn, it.mut_fn)
    )


def _kernel_logic(it: PulseIterator):
    fn = _KERNEL_LOGIC.get(it)
    if fn is None:
        from repro.kernels.pulse_chase import ops as chase_ops

        fn = _KERNEL_LOGIC[it] = chase_ops.iterator_logic(it)
    return fn


def _local_superstep(
    it: PulseIterator,
    pool: jnp.ndarray,  # (L, R) local request pool
    arena_rows: jnp.ndarray,  # (rows_per_shard, W) this shard's arena rows
    bounds: jnp.ndarray,  # (P+1,) switch base table (replicated)
    perms: jnp.ndarray,  # (P,)   protection bits (replicated)
    my_shard: jnp.ndarray,  # () int32
    *,
    k_local: int,
    max_iters: int,
    adaptive: bool = False,
    logic_fn=None,
    rep=None,
    elide_access_check: bool = False,
):
    """Run up to ``k_local`` iterations for locally-owned ACTIVE requests.

    ``adaptive=True`` exits as soon as no record can make local progress
    (active, locally owned, non-NULL): the remaining iterations would be
    identities, so results are bit-identical while remote-heavy supersteps
    stop paying for dead chase work.  ``logic_fn`` substitutes the
    pulse_chase kernel's vectorized iterator body for the per-lane vmap.

    ``rep = (rep_rows, primary_arr, dead_mask, policy)`` enables hot-shard
    replica serving: this shard additionally chases records whose pointer
    lands in its mirrored primary's range (always under ``"spread"``, only
    while the primary is dead under ``"failover"``), reading from its
    replica rows.  A shard marked dead in ``dead_mask`` refuses service on
    its *own* range -- its arena is the one that failed.

    ``elide_access_check=True`` replaces the per-shard PERM_READ probe with
    constant True.  Only ``distributed_execute`` sets it, and only when the
    iterator's pulse-verify certificate proves the traversal read-only AND
    the host has checked every shard grants PERM_READ -- then the probe is
    constant-true by construction and eliding it is bit-identical.
    """
    S = it.scratch_words
    lo = bounds[my_shard]
    hi = bounds[my_shard + 1]
    if elide_access_check:
        perm_ok = True
    else:
        perm_ok = translation.check_access(perms, my_shard, PERM_READ)
    rep_kwargs = {}
    if rep is not None:
        rep_rows, primary_arr, dead_mask, policy = rep
        num = primary_arr.shape[0]
        prim = primary_arr[my_shard]
        prim_safe = jnp.clip(prim, 0, num - 1)
        holds = (prim >= 0) & ~dead_mask[my_shard]
        rep_on = holds if policy == "spread" else (holds & dead_mask[prim_safe])
        rep_kwargs = dict(
            rep_data=rep_rows,
            rep_lo=bounds[prim_safe],
            rep_hi=bounds[prim_safe + 1],
            rep_base=jnp.int32(0),
            rep_on=rep_on,
            rep_perm_ok=translation.check_access(perms, prim_safe, PERM_READ),
        )
        # a dead shard's own arena is gone: collapse its servable range
        hi = jnp.where(dead_mask[my_shard], lo, hi)

    def step(st):
        ptr, scratch, status, iters = st
        return step_batch(
            it,
            arena_rows,
            ptr,
            scratch,
            status,
            iters,
            max_iters=max_iters,
            local_lo=lo,
            local_hi=hi,
            perm_ok=perm_ok,
            logic_fn=logic_fn,
            **rep_kwargs,
        )

    ptr = pool[:, F_PTR]
    scratch = pool[:, F_SCRATCH:]
    status = pool[:, F_STATUS]
    iters = pool[:, F_ITERS]
    if adaptive:
        # chaseable = records a step_batch call could touch (including ones
        # that would fault on the protection check): skipping is only legal
        # when the iteration is an identity for every record in the pool
        def chaseable(ptr, status):
            return jnp.any(
                (status == STATUS_ACTIVE) & (ptr >= lo) & (ptr < hi) & (ptr != NULL)
            )

        def cond(st):
            i, (ptr, _, status, _) = st
            return (i < k_local) & chaseable(ptr, status)

        def body(st):
            i, inner = st
            return i + 1, step(inner)

        _, (ptr, scratch, status, iters) = jax.lax.while_loop(
            cond, body, (jnp.int32(0), (ptr, scratch, status, iters))
        )
    else:
        ptr, scratch, status, iters = jax.lax.fori_loop(
            0, k_local, lambda _, st: step(st), (ptr, scratch, status, iters)
        )
    pool = pool.at[:, F_PTR].set(ptr)
    pool = pool.at[:, F_SCRATCH:].set(scratch)
    pool = pool.at[:, F_STATUS].set(status)
    return pool.at[:, F_ITERS].set(iters)


def _commit_phase(pool, rows, heap_row, lo, hi, my_shard, perm_w, *, S, W):
    """Per-shard commit phase: apply every locally-committable staged
    mutation, one at a time, in deterministic (class, slot, id) order.

    This is the write path's serialization point -- the stand-in for the
    paper's per-node lock.  All chases in a superstep ran *before* this
    phase, so readers see a consistent pre-commit snapshot; concurrent
    writers to one shard serialize through the sorted scatter below
    (stores/CAS first by target slot then request id, then frees, then
    allocs by id -- so a slot freed this phase is immediately reusable by a
    later alloc, exactly like the sequential oracle).  A shard whose range
    lost PERM_WRITE faults every eligible commit instead of applying it.

    Returns ``(pool, rows, heap_row)`` -- arena rows and the heap registers
    [free_head, bump, epoch, commits] are carried state, not loop
    invariants, from here on.
    """
    MB = F_SCRATCH + S
    L = pool.shape[0]
    m_op = pool[:, MB]
    m_tgt = pool[:, MB + 1]
    status = pool[:, F_STATUS]
    pend = (m_op != M_NONE) & (status != STATUS_EMPTY)
    is_alloc = m_op == M_ALLOC
    tgt_local = (m_tgt >= lo) & (m_tgt < hi)
    eligible = pend & jnp.where(is_alloc, pool[:, F_HOME] == my_shard, tgt_local)
    ok = jnp.asarray(perm_w)

    def apply_one(order, i, carry):
        pool, rows, free_head, bump = carry
        r = order[i]
        rec = jax.lax.dynamic_index_in_dim(pool, r, 0, keepdims=False)
        act = eligible[r] & ok
        op = rec[MB]
        tgt = rec[MB + 1]
        data = jax.lax.dynamic_slice(rec, (MB + 4,), (W,))
        maskb = ((rec[MB + 2] >> jnp.arange(W, dtype=jnp.int32)) & 1).astype(bool)

        # STORE / CAS: masked write; CAS guards on the lowest masked word
        toff = jnp.clip(tgt - lo, 0, rows.shape[0] - 1)
        old = jax.lax.dynamic_index_in_dim(rows, toff, 0, keepdims=False)
        cas_ok = old[jnp.argmax(maskb).astype(jnp.int32)] == rec[MB + 3]
        do_store = act & ((op == M_STORE) | ((op == M_CAS) & cas_ok))
        # FREE: zero the slot, word 0 becomes the free-list link
        do_free = act & (op == M_FREE)
        freed = jnp.zeros((W,), jnp.int32).at[0].set(free_head)
        newrow = jnp.where(do_store, jnp.where(maskb, data, old),
                           jnp.where(do_free, freed, old))
        rows = jax.lax.dynamic_update_index_in_dim(rows, newrow, toff, 0)
        free_head = jnp.where(do_free, tgt, free_head)

        # ALLOC: pop the free list, else bump; exhaustion faults the record
        do_alloc = act & (op == M_ALLOC)
        have_free = free_head != NULL
        slot = jnp.where(have_free, free_head, bump)
        can = have_free | (bump < hi)
        aoff = jnp.clip(slot - lo, 0, rows.shape[0] - 1)
        arow = jax.lax.dynamic_index_in_dim(rows, aoff, 0, keepdims=False)
        next_free = arow[0]
        fresh = jnp.where(maskb, data, 0)
        rows = jax.lax.dynamic_update_index_in_dim(
            rows, jnp.where(do_alloc & can, fresh, arow), aoff, 0
        )
        free_head = jnp.where(do_alloc & can & have_free, next_free, free_head)
        bump = jnp.where(do_alloc & can & ~have_free, bump + 1, bump)
        # the claimed global address lands in scratch[m_tgt]
        sidx = F_SCRATCH + jnp.clip(tgt, 0, S - 1)
        rec = rec.at[sidx].set(jnp.where(do_alloc & can, slot, rec[sidx]))
        rec = rec.at[F_STATUS].set(
            jnp.where(do_alloc & ~can, jnp.int32(STATUS_FAULT), rec[F_STATUS])
        )
        rec = rec.at[MB].set(jnp.where(act, jnp.int32(M_NONE), rec[MB]))
        pool = jax.lax.dynamic_update_index_in_dim(pool, rec, r, 0)
        return pool, rows, free_head, bump

    # the serialized scatter (and its 4-pass stable lexsort) only runs when
    # this shard actually has work: commit-free supersteps (most of them, in
    # mixed batches) skip it entirely, the way the read path's lax.cond
    # skips the fabric -- applying zero commits is the identity, so results
    # are unchanged
    def run_commits(carry):
        # lexsort via successive stable sorts, least-significant key first:
        # final order = (ineligible-last, class, slot, id)
        klass = jnp.where(
            is_alloc, 2, jnp.where(m_op == M_FREE, 1, 0)
        ).astype(jnp.int32)
        slot_key = jnp.where(is_alloc, 0, m_tgt)
        order = jnp.arange(L, dtype=jnp.int32)
        for key in (pool[:, F_ID], slot_key, klass, (~eligible).astype(jnp.int32)):
            order = order[jnp.argsort(key[order], stable=True)]
        return jax.lax.fori_loop(
            0, L, lambda i, c: apply_one(order, i, c), carry
        )

    pool, rows, free_head, bump = jax.lax.cond(
        eligible.any(),
        run_commits,
        lambda carry: carry,
        (pool, rows, heap_row[H_FREE], heap_row[H_BUMP]),
    )
    # write-permission fault: eligible commits on a write-revoked shard
    denied = eligible & ~ok
    pool = pool.at[:, F_STATUS].set(
        jnp.where(denied, jnp.int32(STATUS_FAULT), pool[:, F_STATUS])
    )
    pool = pool.at[:, MB].set(jnp.where(denied, jnp.int32(M_NONE), pool[:, MB]))
    n_applied = (eligible & ok).sum().astype(jnp.int32)
    heap_row = heap_row.at[H_FREE].set(free_head)
    heap_row = heap_row.at[H_BUMP].set(bump)
    heap_row = heap_row.at[H_EPOCH].add((n_applied > 0).astype(jnp.int32))
    heap_row = heap_row.at[H_COMMITS].add(n_applied)
    return pool, rows, heap_row


def _local_superstep_mut(
    it: PulseIterator,
    pool: jnp.ndarray,  # (L, R) local request pool (with mutation payload)
    arena_rows: jnp.ndarray,  # (rows_per_shard, W): carried state, not invariant
    heap_row: jnp.ndarray,  # (HEAP_WORDS,) this shard's allocator registers
    bounds: jnp.ndarray,
    perms: jnp.ndarray,
    my_shard: jnp.ndarray,
    *,
    k_local: int,
    max_iters: int,
    adaptive: bool = False,
    commit: bool = True,
):
    """Write-path twin of ``_local_superstep``: chase with write-stalls, then
    (optionally) run this shard's commit phase.

    ``commit=False`` runs the chase only -- the wavefront-pipelined schedule
    chases its two wavefronts separately, merges, and commits the merged
    pool, which is bit-identical to the fused chase-then-commit because the
    commit order is keyed on (class, slot, id), never on pool layout.
    """
    S = it.scratch_words
    W = arena_rows.shape[1]
    MB = F_SCRATCH + S
    lo = bounds[my_shard]
    hi = bounds[my_shard + 1]
    perm_ok = translation.check_access(perms, my_shard, PERM_READ)

    def step(st):
        ptr, scratch, status, iters, mut = st
        return mut_step_batch(
            it, arena_rows, ptr, scratch, status, iters, mut,
            max_iters=max_iters, local_lo=lo, local_hi=hi, perm_ok=perm_ok,
        )

    ptr = pool[:, F_PTR]
    scratch = pool[:, F_SCRATCH:MB]
    status = pool[:, F_STATUS]
    iters = pool[:, F_ITERS]
    mut = pool[:, MB:]
    if adaptive:
        def chaseable(ptr, status, mut):
            return jnp.any(
                (status == STATUS_ACTIVE) & (ptr >= lo) & (ptr < hi)
                & (ptr != NULL) & (mut[:, 0] == M_NONE)
            )

        def cond(st):
            i, (ptr, _, status, _, mut) = st
            return (i < k_local) & chaseable(ptr, status, mut)

        def body(st):
            i, inner = st
            return i + 1, step(inner)

        _, (ptr, scratch, status, iters, mut) = jax.lax.while_loop(
            cond, body, (jnp.int32(0), (ptr, scratch, status, iters, mut))
        )
    else:
        ptr, scratch, status, iters, mut = jax.lax.fori_loop(
            0, k_local, lambda _, st: step(st), (ptr, scratch, status, iters, mut)
        )
    # exhausted-budget sweep: a record can sit ACTIVE at iters >= max_iters
    # only via the pending-mutation MAXED suppression; once its commit
    # clears it must retire before the router sees it again.  The fixed
    # k_local chase touches the whole pool every call so mut_step_batch's
    # own check covers it, but the adaptive chase legally runs *zero*
    # iterations when nothing is locally chaseable -- without this sweep
    # the record would take one more (schedule-dependent) fabric hop before
    # a chase finally touches it, breaking cross-schedule bit-identity.
    status = jnp.where(
        (status == STATUS_ACTIVE) & (iters >= max_iters) & (mut[:, 0] == M_NONE),
        jnp.int32(STATUS_MAXED),
        status,
    )
    pool = pool.at[:, F_PTR].set(ptr)
    pool = pool.at[:, F_SCRATCH:MB].set(scratch)
    pool = pool.at[:, F_STATUS].set(status)
    pool = pool.at[:, F_ITERS].set(iters)
    pool = pool.at[:, MB:].set(mut)
    if not commit:
        return pool
    perm_w = translation.check_access(perms, my_shard, PERM_WRITE)
    return _commit_phase(
        pool, arena_rows, heap_row, lo, hi, my_shard, perm_w, S=S, W=W
    )


def _drop_mask(
    L: int, drop_prob: float, drop_seed: int, my_shard, step_idx
) -> jnp.ndarray:
    """Fault-injection fabric loss: each pool slot is independently 'lost'
    with probability ``drop_prob`` this superstep.  The mask is a pure
    function of (seed, shard, superstep), so injected-loss runs replay
    bit-identically.  A dropped record parks on its source shard and is
    retransmitted next superstep (link-level loss + retransmit), so no
    traversal state is ever lost -- only superstep counts grow."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(drop_seed), my_shard),
        jnp.asarray(step_idx, jnp.int32),
    )
    return jax.random.uniform(key, (L,)) < drop_prob


def _route_decide(
    pool: jnp.ndarray,  # (L, R)
    bounds: jnp.ndarray,
    my_shard: jnp.ndarray,
    num_shards: int,
    *,
    return_to_cpu: bool,
    link_capacity=None,
    phys_capacity: int | None = None,
    drain_done: bool = False,
    mut_base: int | None = None,
    drop_mask: jnp.ndarray | None = None,
    rep_ctx=None,
):
    """Switch decision + leaver extraction: the collective-free half of a
    routed superstep.

    ``rep_ctx = (replica_arr, dead_mask, policy)`` applies the replica
    serve map (``_serve_shard``) to ACTIVE reads: a record bound for a
    dead (or spread-balanced) primary is delivered to the shard holding
    its replica instead.  Faults are still judged on the raw owner -- an
    unmappable pointer is a switch fault regardless of replication.

    Computes each record's next shard, marks switch-level faults, packs the
    records that fit under the per-link capacity into a ``(P, Cp, R)`` send
    buffer, and strips them from the local pool.  Returns ``(kept, send,
    n_routed)`` where ``kept`` is the pool with departed records blanked.
    The wavefront-pipelined schedule calls this directly so the send buffer
    can stay in flight across a loop tick; ``_route`` composes it with
    ``_exchange`` + ``_merge_pools`` for the bulk-synchronous schedule.

    ``mut_base`` (write path) is the column where the mutation payload
    starts: a record with a staged mutation routes to the shard that owns
    its *commit target* (the ALLOC target is the record's home shard), not
    to ``cur_ptr``'s owner -- the staged write rides the fabric to where it
    can serialize.  An unmappable commit target is a switch-level fault.
    """
    L, R = pool.shape
    if phys_capacity is None:
        phys_capacity = L // num_shards if link_capacity is None else int(link_capacity)
    Cp = int(phys_capacity)  # static: buffer rows per destination link
    C = Cp if link_capacity is None else link_capacity  # may be traced
    status = pool[:, F_STATUS]
    valid = status != STATUS_EMPTY
    active = status == STATUS_ACTIVE

    if mut_base is not None:
        m_op = pool[:, mut_base]
        pendm = m_op != M_NONE
        is_alloc = m_op == M_ALLOC
        towner = translation.owner_of(bounds, pool[:, mut_base + 1])
    else:
        pendm = jnp.zeros((L,), bool)

    owner = translation.owner_of(bounds, pool[:, F_PTR])
    # invalid pointer (owner == NULL) on an active request -> the switch
    # notifies the CPU node (Fig. 6 step 6): mark FAULT, send home.  A
    # write-pending record is judged on its commit target instead.
    bad = active & (owner == NULL) & ~pendm
    if mut_base is not None:
        bad_mut = active & pendm & ~is_alloc & (towner == NULL)
        bad = bad | bad_mut
        pool = pool.at[:, mut_base].set(
            jnp.where(bad_mut, jnp.int32(M_NONE), m_op)
        )
        pendm = pendm & ~bad_mut
    status = jnp.where(bad, jnp.int32(3), status)  # STATUS_FAULT
    pool = pool.at[:, F_STATUS].set(status)
    active = status == STATUS_ACTIVE

    serve = _serve_shard(owner, pool[:, F_ID], rep_ctx)
    if return_to_cpu:
        # PULSE-ACC (Fig. 9): a traversal leaving this node must return to its
        # home (CPU) node, which re-issues it -- route non-local actives home.
        stay = active & (owner == my_shard)
        dest = jnp.where(stay, my_shard, pool[:, F_HOME])
        dest = jnp.where(active & (owner != my_shard), pool[:, F_HOME], dest)
        # once home, re-issue toward the owner
        at_home = active & (pool[:, F_HOME] == my_shard) & (owner != my_shard)
        dest = jnp.where(at_home, owner, dest)
    elif drain_done:
        dest = jnp.where(active, serve, my_shard)
    else:
        dest = jnp.where(active, serve, pool[:, F_HOME])
    if mut_base is not None:
        # staged mutations route to their commit shard (ALLOC -> home)
        cdest = jnp.where(is_alloc, pool[:, F_HOME], towner)
        dest = jnp.where(active & pendm, cdest, dest)
    dest = jnp.where(valid, dest, my_shard).astype(jnp.int32)

    moves = valid & (dest != my_shard)

    # pack into (P, Cp+1, R): overflow beyond per-link capacity parks in the
    # trash row (Cp) and stays local for the next superstep.
    onehot = (dest[:, None] == jnp.arange(num_shards, dtype=jnp.int32)[None, :]) & (
        moves[:, None]
    )
    pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - onehot.astype(jnp.int32)
    pos = jnp.take_along_axis(pos, jnp.clip(dest, 0, num_shards - 1)[:, None], axis=1)[
        :, 0
    ]
    fits = moves & (pos < C)
    if drop_mask is not None:
        # injected fabric loss: a dropped record parks locally exactly like
        # capacity overflow and retransmits next superstep -- hops do not
        # advance, so the eventual successful crossing keeps the record's
        # final state bit-identical to a loss-free run
        fits = fits & ~drop_mask
    # a crossing is a record that actually leaves this shard: parked overflow
    # (pos >= C) stays local and must not count toward Fig. 2c/9 crossings
    pool = pool.at[:, F_HOPS].set(pool[:, F_HOPS] + fits.astype(jnp.int32))
    d_idx = jnp.where(fits, dest, 0)
    p_idx = jnp.where(fits, pos, Cp)
    send = jnp.broadcast_to(
        empty_records(1, R - F_SCRATCH)[0], (num_shards, Cp + 1, R)
    ).astype(jnp.int32)
    send = send.at[d_idx, p_idx].set(jnp.where(fits[:, None], pool, send[d_idx, p_idx]))
    send = send[:, :Cp]

    # what leaves this shard is removed from the local pool
    kept = pool.at[:, F_STATUS].set(
        jnp.where(fits, jnp.int32(STATUS_EMPTY), pool[:, F_STATUS])
    )
    return kept, send, fits.sum()


def _exchange(
    send: jnp.ndarray,  # (P, Cp, R) per-destination send buffer
    axis_name: str,
    num_shards: int,
    *,
    fabric: str = "dense",
    my_shard=None,
):
    """Carry the packed send buffer across the fabric; returns arrivals
    ``(P * Cp, R)`` ordered by source shard (dense all_to_all layout).

    ``fabric="dense"`` is the paper's programmable-switch model: one
    all_to_all carries every link at once.  ``fabric="ring"`` decomposes the
    same exchange into ``P - 1`` ``lax.ppermute`` distance classes -- hop h
    carries exactly the records travelling h shards forward, so each hop's
    live payload shrinks with the compaction ladder (the capacity rung gates
    how many records occupy each (Cp, R) hop buffer).  Arrivals are
    assembled into the dense layout, so downstream merges (and therefore
    results, pool layouts, and stats) are bit-identical across fabrics.
    """
    Cp, R = send.shape[1], send.shape[2]
    if fabric == "dense":
        arrivals = jax.lax.all_to_all(
            send, axis_name, split_axis=0, concat_axis=0, tiled=True
        )
        return arrivals.reshape(num_shards * Cp, R)
    if fabric != "ring":
        raise ValueError(f"unknown fabric {fabric!r}")
    # ring: my own (always-empty) self block stays in place; distance class h
    # ships send[(me+h) % P] forward h hops and receives from (me-h) % P.
    arrivals = jnp.broadcast_to(
        empty_records(1, R - F_SCRATCH)[0], (num_shards, Cp, R)
    ).astype(jnp.int32)
    me = my_shard
    for h in range(1, num_shards):
        out = jax.lax.dynamic_index_in_dim(
            send, (me + h) % num_shards, axis=0, keepdims=False
        )
        got = jax.lax.ppermute(
            out, axis_name, perm=[(i, (i + h) % num_shards) for i in range(num_shards)]
        )
        arrivals = jax.lax.dynamic_update_index_in_dim(
            arrivals, got, (me - h) % num_shards, axis=0
        )
    return arrivals.reshape(num_shards * Cp, R)


def _merge_pools(kept: jnp.ndarray, arrivals: jnp.ndarray, L: int):
    """Merge arrivals into the local pool: valid records first, then empties;
    keep L slots (conservation: total valid records across the mesh is
    constant == B <= sum of pools).  Returns ``(merged, n_dropped_valid)``.
    """
    both = jnp.concatenate([kept, arrivals], axis=0)
    is_empty = both[:, F_STATUS] == STATUS_EMPTY
    order = jnp.argsort(is_empty, stable=True)
    merged = both[order][:L]
    n_dropped_valid = (~is_empty).sum() - (merged[:, F_STATUS] != STATUS_EMPTY).sum()
    return merged, n_dropped_valid


def _route(
    pool: jnp.ndarray,  # (L, R)
    bounds: jnp.ndarray,
    my_shard: jnp.ndarray,
    num_shards: int,
    axis_name: str,
    *,
    return_to_cpu: bool,
    link_capacity=None,
    phys_capacity: int | None = None,
    drain_done: bool = False,
    fabric: str = "dense",
    mut_base: int | None = None,
    drop_mask: jnp.ndarray | None = None,
    rep_ctx=None,
):
    """Switch routing: deliver records to their next shard in one superstep.

    ``link_capacity`` is the per-destination link budget C (records per
    superstep); the default is the worst-case L // num_shards.  Compacted
    execution passes a shrunken C once most of the batch has finished, so the
    BSP payload tracks the live set instead of the original batch.  It may be
    a *traced* scalar (the fused loop carries the capacity-ladder rung as
    state); then ``phys_capacity`` fixes the static buffer shape and C only
    gates which records fit -- the parking schedule is identical to a
    host-dispatched superstep compiled at capacity C, so results (and even
    pool layouts) match bit-for-bit.

    ``drain_done`` is the active-set compaction: finished (DONE/FAULT/MAXED)
    records retire *in place* instead of being routed to their home shard --
    the final gather collects them from wherever they stopped, so shipping
    them home only burned link capacity (exactly the waste the paper's switch
    design avoids by keeping only live traversals in the fabric).
    """
    L = pool.shape[0]
    kept, send, n_routed = _route_decide(
        pool, bounds, my_shard, num_shards,
        return_to_cpu=return_to_cpu,
        link_capacity=link_capacity,
        phys_capacity=phys_capacity,
        drain_done=drain_done,
        mut_base=mut_base,
        drop_mask=drop_mask,
        rep_ctx=rep_ctx,
    )
    arrivals = _exchange(
        send, axis_name, num_shards, fabric=fabric, my_shard=my_shard
    )
    merged, n_dropped_valid = _merge_pools(kept, arrivals, L)
    return merged, n_routed, n_dropped_valid


def _remote_active(pool, bounds, my_shard, mut_base: int | None = None, rep_ctx=None):
    """Active records this shard cannot serve (owner elsewhere / invalid).

    A write-pending record's effective destination is its commit shard
    (ALLOC -> home), so a staged remote write keeps the fabric scheduled
    even when every cur_ptr is local.  Under replication the serve map
    decides remoteness, so a record bound for a dead primary's replica
    keeps the fabric scheduled too."""
    active = pool[:, F_STATUS] == STATUS_ACTIVE
    owner = translation.owner_of(bounds, pool[:, F_PTR])
    if mut_base is not None:
        m_op = pool[:, mut_base]
        pendm = m_op != M_NONE
        towner = jnp.where(
            m_op == M_ALLOC,
            pool[:, F_HOME],
            translation.owner_of(bounds, pool[:, mut_base + 1]),
        )
        owner = jnp.where(pendm, towner, owner)
    else:
        owner = _serve_shard(owner, pool[:, F_ID], rep_ctx)
    return (active & (owner != my_shard)).sum()


def make_superstep(
    it: PulseIterator,
    num_shards: int,
    axis_name: str,
    *,
    k_local: int,
    max_iters: int,
    return_to_cpu: bool = False,
    link_capacity: int | None = None,
    drain_done: bool = False,
    do_route: bool = True,
    fabric: str = "dense",
    local_backend: str = "xla",
    mutate: bool = False,
    drop_prob: float = 0.0,
    drop_seed: int = 0,
    replication: ReplicaPlan | None = None,
    elide_access_check: bool = False,
):
    """Builds the jittable per-shard superstep: local run -> switch route.

    ``replication`` (read path only) adds two operands after ``perms`` --
    the holder-sharded replica rows and the traced ``dead_mask`` -- and
    applies the plan's serve map to chase and route decisions.

    ``do_route=False`` builds the compacted *local-only* superstep: when every
    surviving traversal is already at its owning shard, the fabric has nothing
    to carry, so the all_to_all is skipped entirely (wire payload 0).  The
    step still reports how many actives turned remote so the driver knows
    when to re-enter the routed variant.

    Returns ``(pool, n_active, n_routed, n_drop, n_remote)`` -- all counters
    globally psum'd.  ``mutate=True`` builds the write-path superstep: the
    arena rows and heap registers become carried state (chase -> commit ->
    route), and the step signature grows to
    ``(pool, arena_rows, heap, bounds, perms) -> (pool, arena_rows, heap,
    counters...)``.

    ``drop_prob > 0`` (fault injection) adds one trailing traced ``step_idx``
    operand: each routed record is parked with probability ``drop_prob``
    under a (drop_seed, shard, step_idx)-keyed mask (see ``_drop_mask``).
    Production callers leave the default and pay nothing.
    """
    logic_fn = _kernel_logic(it) if local_backend == "kernel" else None
    mut_base = F_SCRATCH + it.scratch_words if mutate else None
    inject_drop = drop_prob > 0.0 and do_route
    if replication is not None and mutate:
        raise ValueError("replication is a read-path feature (writes park)")
    if replication is not None:
        primary_arr = jnp.asarray(replication.primary_map, jnp.int32)
        replica_arr = jnp.asarray(replication.replica_map, jnp.int32)

    def _mask(pool, my_shard, fault_args):
        if not inject_drop:
            return None
        return _drop_mask(
            pool.shape[0], drop_prob, drop_seed, my_shard, fault_args[0]
        )

    def superstep(pool, arena_rows, bounds, perms, *extra):
        CACHE_STATS.traces += 1  # trace-time side effect: counts recompiles
        my_shard = jax.lax.axis_index(axis_name).astype(jnp.int32)
        if replication is not None:
            rep_rows, dead_mask, *fault_args = extra
            rep = (rep_rows, primary_arr, dead_mask, replication.policy)
            rep_ctx = (replica_arr, dead_mask, replication.policy)
        else:
            fault_args = extra
            rep = rep_ctx = None
        pool = _local_superstep(
            it, pool, arena_rows, bounds, perms, my_shard,
            k_local=k_local, max_iters=max_iters, logic_fn=logic_fn, rep=rep,
            elide_access_check=elide_access_check,
        )
        if do_route:
            pool, n_routed, n_drop = _route(
                pool, bounds, my_shard, num_shards, axis_name,
                return_to_cpu=return_to_cpu,
                link_capacity=link_capacity,
                drain_done=drain_done,
                fabric=fabric,
                drop_mask=_mask(pool, my_shard, fault_args),
                rep_ctx=rep_ctx,
            )
        else:
            n_routed = jnp.int32(0)
            n_drop = jnp.int32(0)
        n_active = (pool[:, F_STATUS] == STATUS_ACTIVE).sum()
        n_remote = _remote_active(pool, bounds, my_shard, rep_ctx=rep_ctx)
        n_active = jax.lax.psum(n_active, axis_name)
        n_routed = jax.lax.psum(n_routed, axis_name)
        n_drop = jax.lax.psum(n_drop, axis_name)
        n_remote = jax.lax.psum(n_remote, axis_name)
        return pool, n_active, n_routed, n_drop, n_remote

    def superstep_mut(pool, arena_rows, heap, bounds, perms, *fault_args):
        CACHE_STATS.traces += 1  # trace-time side effect: counts recompiles
        my_shard = jax.lax.axis_index(axis_name).astype(jnp.int32)
        pool, arena_rows, heap_row = _local_superstep_mut(
            it, pool, arena_rows, heap[0], bounds, perms, my_shard,
            k_local=k_local, max_iters=max_iters,
        )
        heap = heap_row[None, :]
        if do_route:
            pool, n_routed, n_drop = _route(
                pool, bounds, my_shard, num_shards, axis_name,
                return_to_cpu=return_to_cpu,
                link_capacity=link_capacity,
                drain_done=drain_done,
                fabric=fabric,
                mut_base=mut_base,
                drop_mask=_mask(pool, my_shard, fault_args),
            )
        else:
            n_routed = jnp.int32(0)
            n_drop = jnp.int32(0)
        n_active = (pool[:, F_STATUS] == STATUS_ACTIVE).sum()
        n_remote = _remote_active(pool, bounds, my_shard, mut_base)
        n_active = jax.lax.psum(n_active, axis_name)
        n_routed = jax.lax.psum(n_routed, axis_name)
        n_drop = jax.lax.psum(n_drop, axis_name)
        n_remote = jax.lax.psum(n_remote, axis_name)
        return pool, arena_rows, heap, n_active, n_routed, n_drop, n_remote

    return superstep_mut if mutate else superstep


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def _pow2_at_least_traced(n: jnp.ndarray) -> jnp.ndarray:
    """Traced twin of ``_pow2_at_least``: exact integer bit-length (no float
    log2, whose rounding at exact powers of two would desync the fused
    capacity ladder from the host-dispatched one)."""
    bl = jnp.sum(
        (jnp.asarray(n, jnp.int32) - 1) >= (1 << jnp.arange(31, dtype=jnp.int32))
    ).astype(jnp.int32)
    return jnp.left_shift(jnp.int32(1), bl)


def _ladder_traced(
    n_active, n_remote, *, num_shards: int, base_capacity: int,
    min_link_capacity: int, compact: bool,
):
    """The host loop's capacity ladder on traced stale-by-one counts --
    the ONE definition every device-resident schedule (fused, pipelined)
    must share, or their wire accounting and pool layouts desync.
    Returns ``(capacity, do_route)``."""
    if not compact:
        return jnp.int32(base_capacity), jnp.bool_(True)
    demand = (n_active + num_shards - 1) // num_shards
    capacity = jnp.minimum(
        jnp.int32(base_capacity),
        jnp.maximum(jnp.int32(min_link_capacity), _pow2_at_least_traced(demand)),
    )
    return capacity, n_remote > 0


# Compiled-executable caches, shared by every distributed_execute caller
# (PulseEngine.execute, PulseService quanta, benchmarks): per-hop supersteps
# keyed by (iterator, mesh, capacity rung, ...), fused whole-traversal loops
# keyed by (iterator, mesh, pool shape, record width, ...).  CACHE_STATS
# tracks hits/misses/traces for the retracing regression tests.
_STEP_CACHE: dict = {}
_FUSED_CACHE: dict = {}

# Device-resident arenas: (id(arena), mesh, axis_name) -> sharded
# (data, bounds, perms).  A PulseService quantum re-enters distributed_execute
# every scheduling round with the same arena; placing the pool once and
# reusing the resident buffers removes the per-quantum H2D re-upload.
_RESIDENT: dict = {}


def reset_executable_caches() -> None:
    """Drop every cached executable / resident buffer (test isolation)."""
    _STEP_CACHE.clear()
    _FUSED_CACHE.clear()
    _RESIDENT.clear()
    CACHE_STATS.reset()


def _resident_arena(arena: Arena, mesh: Mesh, axis_name: str):
    key = (id(arena), mesh, axis_name)
    ent = _RESIDENT.get(key)
    if ent is None:
        ent = (
            jax.device_put(arena.data, NamedSharding(mesh, P(axis_name, None))),
            jax.device_put(arena.bounds, NamedSharding(mesh, P())),
            jax.device_put(arena.perms, NamedSharding(mesh, P())),
        )
        _RESIDENT[key] = ent
        # evict when the arena dies so a recycled id() cannot alias stale data
        weakref.finalize(arena, _RESIDENT.pop, key, None)
    return ent


def make_fused_loop(
    it: PulseIterator,
    num_shards: int,
    axis_name: str,
    *,
    k_local: int,
    max_supersteps: int,
    base_capacity: int,
    min_link_capacity: int,
    return_to_cpu: bool,
    compact: bool,
    fabric: str = "dense",
    local_backend: str = "xla",
    mutate: bool = False,
    drop_prob: float = 0.0,
    drop_seed: int = 0,
    elide_access_check: bool = False,
):
    """Builds the whole-traversal device-resident loop (one shard's view).

    The entire superstep schedule -- local execution, the local-vs-fabric
    decision, the power-of-two capacity ladder, and termination -- runs as a
    single ``lax.while_loop``; the host only sees the final pool and a handful
    of aggregate counters.  Scheduling decisions mirror ``distributed_execute``
    's host loop exactly (same stale-by-one active/remote counts, same ladder
    arithmetic), so the fused execution is bit-identical to the dispatched
    one, down to pool layouts and crossing counts.

    Returned state: ``(pool, n_active, steps, routed, dropped, cap_counts,
    local_only)`` -- every counter globally psum'd/replicated.  ``cap_counts``
    is a histogram of routed supersteps per capacity rung (the ladder has at
    most 31 distinct values, precomputed in ``capacity_rungs``); the host
    turns it into a wire-word total with Python integer arithmetic, so the
    traced counters never multiply capacity into an int32 (which would wrap
    at production batch sizes where the dispatched path's per-step Python
    sums would not).

    The per-record iteration budget (the serving layer's *quantum*) is a
    traced ``iter_budget`` operand, not a trace constant: SLO-aware quantum
    sizing re-enters the same compiled executable every scheduling round
    with a different budget, so baking it into the trace would recompile
    per quantum value.

    ``halt`` (the second trailing traced operand, fault injection) caps the
    loop at ``halt`` supersteps: an armed shard-kill passes
    ``kill_superstep - 1`` so the loop exits cleanly with records still
    ACTIVE, and the host raises ``ShardFailure`` instead of the
    still-ACTIVE error.  Unarmed callers pass ``max_supersteps``, which the
    loop condition already enforces -- zero-cost default.

    ``drop_prob > 0`` parks each routed record with that probability under
    a (drop_seed, shard, superstep)-keyed mask (see ``_drop_mask``).
    """
    drain_done = compact
    rungs = capacity_rungs(base_capacity, min_link_capacity) if compact else (
        base_capacity,
    )
    rungs_arr = jnp.asarray(rungs, jnp.int32)
    logic_fn = _kernel_logic(it) if local_backend == "kernel" else None
    mut_base = F_SCRATCH + it.scratch_words if mutate else None
    inject_drop = drop_prob > 0.0

    def _mask(L, my_shard, steps):
        if not inject_drop:
            return None
        return _drop_mask(L, drop_prob, drop_seed, my_shard, steps)

    def fused_mut(pool, arena_rows, heap, bounds, perms, iter_budget, halt):
        """Write-path fused loop: arena rows + heap registers are carried
        ``lax.while_loop`` state -- each superstep is chase -> commit ->
        route, with the same ladder decisions as the read path."""
        CACHE_STATS.traces += 1  # trace-time side effect: counts recompiles
        my_shard = jax.lax.axis_index(axis_name).astype(jnp.int32)
        n0 = jax.lax.psum(
            (pool[:, F_STATUS] == STATUS_ACTIVE).sum().astype(jnp.int32), axis_name
        )

        def cond(carry):
            _, _, _, n_active, steps, _, n_drop, _, _, _ = carry
            return (
                (n_active > 0) & (steps < max_supersteps) & (n_drop == 0)
                & (steps < halt)
            )

        def body(carry):
            (pool, rows, heap, n_active, steps, n_routed_tot, n_drop_tot,
             cap_counts, local_only, n_remote) = carry
            pool, rows, heap_row = _local_superstep_mut(
                it, pool, rows, heap[0], bounds, perms, my_shard,
                k_local=k_local, max_iters=iter_budget,
            )
            heap = heap_row[None, :]
            capacity, do_route = _ladder_traced(
                n_active, n_remote, num_shards=num_shards,
                base_capacity=base_capacity,
                min_link_capacity=min_link_capacity, compact=compact,
            )

            def routed(p):
                return _route(
                    p, bounds, my_shard, num_shards, axis_name,
                    return_to_cpu=return_to_cpu,
                    link_capacity=capacity, phys_capacity=base_capacity,
                    drain_done=drain_done, fabric=fabric, mut_base=mut_base,
                    drop_mask=_mask(p.shape[0], my_shard, steps),
                )

            def local_only_step(p):
                return p, jnp.int32(0), jnp.int32(0)

            if compact:
                pool, n_routed, n_drop = jax.lax.cond(
                    do_route, routed, local_only_step, pool
                )
            else:
                pool, n_routed, n_drop = routed(pool)
            n_active = jax.lax.psum(
                (pool[:, F_STATUS] == STATUS_ACTIVE).sum().astype(jnp.int32),
                axis_name,
            )
            n_remote = jax.lax.psum(
                _remote_active(pool, bounds, my_shard, mut_base).astype(jnp.int32),
                axis_name,
            )
            n_routed = jax.lax.psum(n_routed.astype(jnp.int32), axis_name)
            n_drop = jax.lax.psum(n_drop.astype(jnp.int32), axis_name)
            cap_counts = cap_counts + jnp.where(
                do_route, (rungs_arr == capacity).astype(jnp.int32), 0
            )
            local_only = local_only + jnp.where(do_route, 0, 1).astype(jnp.int32)
            return (
                pool, rows, heap, n_active, steps + 1, n_routed_tot + n_routed,
                n_drop_tot + n_drop, cap_counts, local_only, n_remote,
            )

        init = (
            pool, arena_rows, heap, n0, jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.zeros(len(rungs), jnp.int32), jnp.int32(0), n0,
        )
        (pool, rows, heap, n_active, steps, n_routed, n_drop, cap_counts,
         local_only, _) = jax.lax.while_loop(cond, body, init)
        return pool, rows, heap, n_active, steps, n_routed, n_drop, cap_counts, local_only

    def fused(pool, arena_rows, bounds, perms, iter_budget, halt):
        CACHE_STATS.traces += 1  # trace-time side effect: counts recompiles
        my_shard = jax.lax.axis_index(axis_name).astype(jnp.int32)
        n0 = jax.lax.psum(
            (pool[:, F_STATUS] == STATUS_ACTIVE).sum().astype(jnp.int32), axis_name
        )

        def cond(carry):
            _, n_active, steps, _, n_drop, _, _, _ = carry
            return (
                (n_active > 0) & (steps < max_supersteps) & (n_drop == 0)
                & (steps < halt)
            )

        def body(carry):
            pool, n_active, steps, n_routed_tot, n_drop_tot, cap_counts, local_only, n_remote = carry
            pool = _local_superstep(
                it, pool, arena_rows, bounds, perms, my_shard,
                k_local=k_local, max_iters=iter_budget, logic_fn=logic_fn,
                elide_access_check=elide_access_check,
            )
            # the host loop's ladder on stale-by-one counts (shared with the
            # pipelined schedule -- see _ladder_traced)
            capacity, do_route = _ladder_traced(
                n_active, n_remote, num_shards=num_shards,
                base_capacity=base_capacity,
                min_link_capacity=min_link_capacity, compact=compact,
            )

            def routed(p):
                return _route(
                    p, bounds, my_shard, num_shards, axis_name,
                    return_to_cpu=return_to_cpu,
                    link_capacity=capacity, phys_capacity=base_capacity,
                    drain_done=drain_done, fabric=fabric,
                    drop_mask=_mask(p.shape[0], my_shard, steps),
                )

            def local_only_step(p):
                return p, jnp.int32(0), jnp.int32(0)

            if compact:
                # conditional collective: every shard takes the same branch
                # (the predicate is a psum), so the fabric is skipped entirely
                # on local-only supersteps
                pool, n_routed, n_drop = jax.lax.cond(
                    do_route, routed, local_only_step, pool
                )
            else:
                pool, n_routed, n_drop = routed(pool)
            n_active = jax.lax.psum(
                (pool[:, F_STATUS] == STATUS_ACTIVE).sum().astype(jnp.int32),
                axis_name,
            )
            n_remote = jax.lax.psum(
                _remote_active(pool, bounds, my_shard).astype(jnp.int32), axis_name
            )
            n_routed = jax.lax.psum(n_routed.astype(jnp.int32), axis_name)
            n_drop = jax.lax.psum(n_drop.astype(jnp.int32), axis_name)
            cap_counts = cap_counts + jnp.where(
                do_route, (rungs_arr == capacity).astype(jnp.int32), 0
            )
            local_only = local_only + jnp.where(do_route, 0, 1).astype(jnp.int32)
            return (
                pool, n_active, steps + 1, n_routed_tot + n_routed,
                n_drop_tot + n_drop, cap_counts, local_only, n_remote,
            )

        # before the first superstep the host loop assumes everything is
        # active and remote (n_active = n_remote = B); mirror that exactly
        init = (
            pool, n0, jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.zeros(len(rungs), jnp.int32), jnp.int32(0), n0,
        )
        pool, n_active, steps, n_routed, n_drop, cap_counts, local_only, _ = (
            jax.lax.while_loop(cond, body, init)
        )
        return pool, n_active, steps, n_routed, n_drop, cap_counts, local_only

    return fused_mut if mutate else fused


def capacity_rungs(base_capacity: int, min_link_capacity: int) -> tuple:
    """The distinct values the compacted capacity ladder can take: powers of
    two clamped to [min_link_capacity, base_capacity] -- at most 31 rungs."""
    return tuple(
        sorted({
            min(base_capacity, max(min_link_capacity, 1 << i)) for i in range(31)
        })
    )


def make_pipelined_loop(
    it: PulseIterator,
    num_shards: int,
    axis_name: str,
    *,
    k_local: int,
    max_supersteps: int,
    base_capacity: int,
    min_link_capacity: int,
    return_to_cpu: bool,
    compact: bool,
    fabric: str = "dense",
    local_backend: str = "xla",
    mutate: bool = False,
    drop_prob: float = 0.0,
    drop_seed: int = 0,
    elide_access_check: bool = False,
):
    """Wavefront-pipelined whole-traversal loop (one shard's view).

    The fused loop (``make_fused_loop``) still executes each superstep as a
    strict sequence -- chase, then exchange, then wait -- so the fabric idles
    while lanes chase pointers and vice versa.  This schedule splits the
    active set into two wavefronts and double-buffers them across loop
    ticks:

      * **wavefront A (in flight)** -- the records extracted by superstep
        s-1's routing decision ride the fabric as carried loop state (the
        packed send buffer), landing at the *start* of tick s;
      * **wavefront B (resident)** -- everything still in the local pool
        runs superstep s's local chase while A is in flight.  The two have
        no data dependence, so the collective overlaps the chase.

    Then they swap: the landed wavefront chases, merges back, and superstep
    s's routing decision extracts the next in-flight wavefront.  Because a
    record's trajectory is elementwise (chase commutes with the merge
    permutation) and every scheduling decision -- the pow2 capacity ladder,
    the local-vs-fabric cond, parking -- is re-derived from the same merged
    stale-by-one counts as the fused loop, results, pool layouts, superstep
    counts, and wire accounting are bit-identical to the fused schedule and
    the BSP oracle.

    Fabric-side coordination is also leaner: the four per-superstep psums
    collapse into one stacked psum of the two counts the scheduler actually
    needs next tick (active, remote); routed/dropped totals accumulate
    per-wavefront in local registers and merge in a single psum after the
    loop exits.  ``RoutingStats`` wire accounting (cap_counts histogram) is
    identical -- it tracks routing *decisions*, which are schedule-invariant.

    ``fabric="ring"`` carries the in-flight wavefront on ppermute distance
    classes instead of the dense all_to_all; ``local_backend="kernel"``
    threads the local chase through the pulse_chase kernel's vectorized
    iterator body.  Both compose with the overlap schedule.
    """
    drain_done = compact
    rungs = capacity_rungs(base_capacity, min_link_capacity) if compact else (
        base_capacity,
    )
    rungs_arr = jnp.asarray(rungs, jnp.int32)
    Cp = base_capacity
    logic_fn = _kernel_logic(it) if local_backend == "kernel" else None
    mut_base = F_SCRATCH + it.scratch_words if mutate else None
    inject_drop = drop_prob > 0.0

    def _mask(L, my_shard, steps):
        if not inject_drop:
            return None
        return _drop_mask(L, drop_prob, drop_seed, my_shard, steps)

    def pipelined_mut(pool, arena_rows, heap, bounds, perms, iter_budget, halt):
        """Write-path pipelined loop.  The two wavefronts chase separately
        (stalling on staged writes), merge, and THEN the merged pool runs
        this shard's commit phase -- bit-identical to the fused
        chase-then-commit because the commit order is keyed on
        (class, slot, id), never on pool layout.  The in-flight wavefront
        can carry staged mutations: they ride the same send buffer and
        commit where they land."""
        CACHE_STATS.traces += 1  # trace-time side effect: counts recompiles
        my_shard = jax.lax.axis_index(axis_name).astype(jnp.int32)
        L, R = pool.shape
        S = it.scratch_words
        W = arena_rows.shape[1]
        lo = bounds[my_shard]
        hi = bounds[my_shard + 1]
        perm_w = translation.check_access(perms, my_shard, PERM_WRITE)
        n0 = jax.lax.psum(
            (pool[:, F_STATUS] == STATUS_ACTIVE).sum().astype(jnp.int32), axis_name
        )
        empty_send = jnp.broadcast_to(
            empty_records(1, R - F_SCRATCH)[0], (num_shards, Cp, R)
        ).astype(jnp.int32)

        def cond(carry):
            _, _, _, _, _, n_active, _, steps, *_ = carry
            return (n_active > 0) & (steps < max_supersteps) & (steps < halt)

        def body(carry):
            (kept, send, rows, heap, did_route, n_active, n_remote, steps,
             routed_acc, drop_acc, cap_counts, local_only) = carry

            def chase(p):
                return _local_superstep_mut(
                    it, p, rows, heap[0], bounds, perms, my_shard,
                    k_local=k_local, max_iters=iter_budget,
                    adaptive=True, commit=False,
                )

            def land(ops_):
                kept, send = ops_
                arrivals = _exchange(
                    send, axis_name, num_shards, fabric=fabric, my_shard=my_shard
                )
                landed = chase(arrivals)
                resident = chase(kept)
                return _merge_pools(resident, landed, L)

            def stay(ops_):
                kept, _ = ops_
                return chase(kept), jnp.int32(0)

            pool_s, n_drop = jax.lax.cond(did_route, land, stay, (kept, send))

            # the merged pool commits exactly once per tick (the fused
            # schedule's chase-then-commit, reordered across the overlap)
            pool_s, rows, heap_row = _commit_phase(
                pool_s, rows, heap[0], lo, hi, my_shard, perm_w, S=S, W=W
            )
            heap = heap_row[None, :]

            capacity, do_route = _ladder_traced(
                n_active, n_remote, num_shards=num_shards,
                base_capacity=base_capacity,
                min_link_capacity=min_link_capacity, compact=compact,
            )

            def extract(p):
                return _route_decide(
                    p, bounds, my_shard, num_shards,
                    return_to_cpu=return_to_cpu,
                    link_capacity=capacity, phys_capacity=base_capacity,
                    drain_done=drain_done, mut_base=mut_base,
                    drop_mask=_mask(p.shape[0], my_shard, steps),
                )

            def hold(p):
                return p, empty_send, jnp.int32(0)

            if compact:
                kept, send, n_routed = jax.lax.cond(do_route, extract, hold, pool_s)
            else:
                kept, send, n_routed = extract(pool_s)

            inflight = send.reshape(num_shards * Cp, R)
            na_local = (
                (kept[:, F_STATUS] == STATUS_ACTIVE).sum()
                + (inflight[:, F_STATUS] == STATUS_ACTIVE).sum()
            ).astype(jnp.int32)
            nr_local = _remote_active(kept, bounds, my_shard, mut_base).astype(
                jnp.int32
            )
            counts = jax.lax.psum(jnp.stack([na_local, nr_local]), axis_name)

            cap_counts = cap_counts + jnp.where(
                do_route, (rungs_arr == capacity).astype(jnp.int32), 0
            )
            local_only = local_only + jnp.where(do_route, 0, 1).astype(jnp.int32)
            return (
                kept, send, rows, heap, do_route, counts[0], counts[1], steps + 1,
                routed_acc + n_routed, drop_acc + n_drop, cap_counts, local_only,
            )

        init = (
            pool, empty_send, arena_rows, heap, jnp.bool_(False), n0, n0,
            jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.zeros(len(rungs), jnp.int32), jnp.int32(0),
        )
        (kept, send, rows, heap, did_route, n_active, _, steps,
         routed_acc, drop_acc, cap_counts, local_only) = jax.lax.while_loop(
            cond, body, init
        )

        def final_land(ops_):
            kept, send = ops_
            arrivals = _exchange(
                send, axis_name, num_shards, fabric=fabric, my_shard=my_shard
            )
            return _merge_pools(kept, arrivals, kept.shape[0])

        def final_stay(ops_):
            return ops_[0], jnp.int32(0)

        pool_out, n_drop = jax.lax.cond(did_route, final_land, final_stay, (kept, send))

        n_routed = jax.lax.psum(routed_acc, axis_name)
        n_dropped = jax.lax.psum(drop_acc + n_drop, axis_name)
        return (
            pool_out, rows, heap, n_active, steps, n_routed, n_dropped,
            cap_counts, local_only,
        )

    def pipelined(pool, arena_rows, bounds, perms, iter_budget, halt):
        CACHE_STATS.traces += 1  # trace-time side effect: counts recompiles
        my_shard = jax.lax.axis_index(axis_name).astype(jnp.int32)
        L, R = pool.shape
        n0 = jax.lax.psum(
            (pool[:, F_STATUS] == STATUS_ACTIVE).sum().astype(jnp.int32), axis_name
        )
        empty_send = jnp.broadcast_to(
            empty_records(1, R - F_SCRATCH)[0], (num_shards, Cp, R)
        ).astype(jnp.int32)

        def chase(p):
            return _local_superstep(
                it, p, arena_rows, bounds, perms, my_shard,
                k_local=k_local, max_iters=iter_budget,
                adaptive=True, logic_fn=logic_fn,
                elide_access_check=elide_access_check,
            )

        def cond(carry):
            _, _, _, n_active, _, steps, *_ = carry
            return (n_active > 0) & (steps < max_supersteps) & (steps < halt)

        def body(carry):
            (kept, send, did_route, n_active, n_remote, steps,
             routed_acc, drop_acc, cap_counts, local_only) = carry

            # --- land wavefront A while wavefront B chases ----------------
            # Inside the routed branch the exchange consumes only the
            # carried send buffer and the resident chase only the kept
            # pool: independent dataflow, so the collective and the local
            # superstep overlap.  Chase commutes with the merge permutation,
            # so merging after (instead of before, as the fused loop does)
            # is bit-identical.
            def land(ops_):
                kept, send = ops_
                arrivals = _exchange(
                    send, axis_name, num_shards, fabric=fabric, my_shard=my_shard
                )
                landed = chase(arrivals)  # wavefront A chases where it landed
                resident = chase(kept)  # wavefront B chases concurrently
                return _merge_pools(resident, landed, L)

            def stay(ops_):
                kept, _ = ops_
                return chase(kept), jnp.int32(0)

            pool_s, n_drop = jax.lax.cond(did_route, land, stay, (kept, send))

            # --- superstep s's routing decision (the shared ladder) -------
            capacity, do_route = _ladder_traced(
                n_active, n_remote, num_shards=num_shards,
                base_capacity=base_capacity,
                min_link_capacity=min_link_capacity, compact=compact,
            )

            def extract(p):
                return _route_decide(
                    p, bounds, my_shard, num_shards,
                    return_to_cpu=return_to_cpu,
                    link_capacity=capacity, phys_capacity=base_capacity,
                    drain_done=drain_done,
                    drop_mask=_mask(p.shape[0], my_shard, steps),
                )

            def hold(p):
                return p, empty_send, jnp.int32(0)

            if compact:
                kept, send, n_routed = jax.lax.cond(do_route, extract, hold, pool_s)
            else:
                kept, send, n_routed = extract(pool_s)

            # --- one stacked psum carries both scheduler counts -----------
            # n_active spans both wavefronts (in-flight records keep their
            # status in transit); in-flight records head to their owning
            # shard, so they contribute nothing remote under compaction
            # (and n_remote is schedule-dead otherwise).
            inflight = send.reshape(num_shards * Cp, R)
            na_local = (
                (kept[:, F_STATUS] == STATUS_ACTIVE).sum()
                + (inflight[:, F_STATUS] == STATUS_ACTIVE).sum()
            ).astype(jnp.int32)
            nr_local = _remote_active(kept, bounds, my_shard).astype(jnp.int32)
            counts = jax.lax.psum(jnp.stack([na_local, nr_local]), axis_name)

            cap_counts = cap_counts + jnp.where(
                do_route, (rungs_arr == capacity).astype(jnp.int32), 0
            )
            local_only = local_only + jnp.where(do_route, 0, 1).astype(jnp.int32)
            return (
                kept, send, do_route, counts[0], counts[1], steps + 1,
                routed_acc + n_routed, drop_acc + n_drop, cap_counts, local_only,
            )

        init = (
            pool, empty_send, jnp.bool_(False), n0, n0, jnp.int32(0),
            jnp.int32(0), jnp.int32(0), jnp.zeros(len(rungs), jnp.int32),
            jnp.int32(0),
        )
        (kept, send, did_route, n_active, _, steps,
         routed_acc, drop_acc, cap_counts, local_only) = jax.lax.while_loop(
            cond, body, init
        )

        # land the final in-flight wavefront (loop exit leaves the last
        # routing decision's records on the wire; no chase -- either nothing
        # is active, or we hit max_supersteps and the host raises anyway)
        def final_land(ops_):
            kept, send = ops_
            arrivals = _exchange(
                send, axis_name, num_shards, fabric=fabric, my_shard=my_shard
            )
            return _merge_pools(kept, arrivals, kept.shape[0])

        def final_stay(ops_):
            return ops_[0], jnp.int32(0)

        pool_out, n_drop = jax.lax.cond(did_route, final_land, final_stay, (kept, send))

        # per-wavefront accumulators merge in one post-loop psum
        n_routed = jax.lax.psum(routed_acc, axis_name)
        n_dropped = jax.lax.psum(drop_acc + n_drop, axis_name)
        return pool_out, n_active, steps, n_routed, n_dropped, cap_counts, local_only

    return pipelined_mut if mutate else pipelined


def get_fused_runner(
    it: PulseIterator,
    mesh: Mesh,
    axis_name: str,
    *,
    num_shards: int,
    pool_rows: int,
    scratch_words: int,
    k_local: int,
    max_supersteps: int,
    base_capacity: int,
    min_link_capacity: int,
    return_to_cpu: bool,
    compact: bool,
    schedule: str = "fused",
    fabric: str = "dense",
    local_backend: str = "xla",
    mutate: bool = False,
    drop_prob: float = 0.0,
    drop_seed: int = 0,
    elide_access_check: bool = False,
):
    """Cached, jitted, donated whole-traversal executable (fused or
    wavefront-pipelined schedule).

    Key = (iterator, mesh, pool shape, record width, schedule knobs,
    mutability); the capacity rung is *traced state* inside the loop, so the
    ladder costs one executable instead of O(log L).  ``donate_argnums=(0,)``
    hands the request pool's buffer to XLA (it is rebuilt per call, and the
    while_loop aliases it in place); the arena buffers are NOT donated on
    either path -- read-only runs keep them device-resident across calls,
    and mutating runs deliberately leave the *input* snapshot intact (the
    updated rows/heap come back as fresh outputs), so a caller can replay
    the same pre-state through several schedules (the determinism oracle's
    contract).

    The iteration budget (the serving quantum) is NOT part of the key: it
    rides into the executable as a traced int32 operand (the trailing
    argument), so SLO-aware quantum sizing reuses one compiled program for
    every budget value.
    """
    key = (
        it, mesh, axis_name, num_shards, pool_rows, scratch_words, k_local,
        max_supersteps, base_capacity, min_link_capacity,
        return_to_cpu, compact, schedule, fabric, local_backend, mutate,
        drop_prob, drop_seed, elide_access_check,
    )
    fn = _FUSED_CACHE.get(key)
    if fn is None:
        CACHE_STATS.misses += 1
        if schedule == "pipelined":
            loop = make_pipelined_loop(
                it, num_shards, axis_name,
                k_local=k_local,
                max_supersteps=max_supersteps,
                base_capacity=base_capacity,
                min_link_capacity=min_link_capacity,
                return_to_cpu=return_to_cpu, compact=compact,
                fabric=fabric, local_backend=local_backend, mutate=mutate,
                drop_prob=drop_prob, drop_seed=drop_seed,
                elide_access_check=elide_access_check,
            )
        else:
            loop = make_fused_loop(
                it, num_shards, axis_name,
                k_local=k_local,
                max_supersteps=max_supersteps,
                base_capacity=base_capacity,
                min_link_capacity=min_link_capacity,
                return_to_cpu=return_to_cpu, compact=compact,
                fabric=fabric, local_backend=local_backend, mutate=mutate,
                drop_prob=drop_prob, drop_seed=drop_seed,
                elide_access_check=elide_access_check,
            )
        # trailing P() pair: the traced iter_budget and halt scalars
        if mutate:
            in_specs = (
                P(axis_name), P(axis_name), P(axis_name), P(), P(), P(), P(),
            )
            out_specs = (
                P(axis_name), P(axis_name), P(axis_name),
                P(), P(), P(), P(), P(), P(),
            )
        else:
            in_specs = (P(axis_name), P(axis_name), P(), P(), P(), P())
            out_specs = (P(axis_name), P(), P(), P(), P(), P(), P())
        fn = jax.jit(
            shard_map(
                loop, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            ),
            donate_argnums=(0,),
        )
        _FUSED_CACHE[key] = fn
    else:
        CACHE_STATS.hits += 1
    return fn


def can_elide_access_check(it: PulseIterator, arena: Arena) -> bool:
    """True when the per-hop PERM_READ probe is statically constant-true.

    Two proofs combine: the iterator's pulse-verify certificate
    (``it.facts``) shows the traversal only ever reads (``facts.read_only``
    -- no store-class op on any reachable path, so PERM_READ is the entire
    required mask), and a host-side scan shows every shard of
    ``arena.perms`` grants PERM_READ.  Under both, ``check_access`` would
    return True for every pointer the traversal can present -- local,
    remote, or faulting-on-NULL alike -- so replacing the probe with the
    constant is bit-identical.  Unverified iterators (``facts is None``)
    never qualify: absence of a certificate means every conservative
    runtime check stays.
    """
    facts = it.facts
    if facts is None or not getattr(facts, "read_only", False) or it.mutates:
        return False
    TRACE.count("host_syncs")
    perms = np.asarray(arena.perms)
    return bool(np.all((perms & PERM_READ) == PERM_READ))


def distributed_execute(
    it: PulseIterator,
    arena: Arena,
    ptr0: jax.Array,
    scratch0: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = "mem",
    max_iters: int = 1 << 30,
    k_local: int = 4,
    max_supersteps: int = 1 << 16,
    return_to_cpu: bool = False,
    compact: bool = False,
    min_link_capacity: int = 8,
    fused: bool = False,
    schedule: str | None = None,
    fabric: str = "dense",
    local_backend: str = "xla",
    fault_injector=None,
    replication: ReplicaContext | None = None,
    elide_access_check: bool | None = None,
):
    """Run a batch of traversals over a range-partitioned arena on a mesh.

    ``replication`` (read path, dispatched schedule) threads a
    ``ReplicaContext`` through every superstep: the serve map redirects
    reads bound for dead (or spread-balanced) primaries to their replica
    holders, which chase them from their mirrored rows -- replicas are
    bit-identical by construction, so final ``(ptr, scratch, status,
    iters)`` match the failure-free run exactly; only ``hops`` and
    superstep counts may differ (the redirect changes *where* records are
    served, never their state trajectory).  A dead shard with no replica
    simply cannot serve its range -- callers must not route reads there.

    ``schedule`` selects the superstep engine (``fused`` is the boolean
    shorthand kept for callers predating the pipelined schedule):

      * ``"dispatched"`` -- one jitted superstep per hop, scheduling on host;
      * ``"fused"``      -- whole traversal as one device-resident
        ``lax.while_loop`` (chase, then exchange, strictly in sequence);
      * ``"pipelined"``  -- the fused loop's active set split into two
        wavefronts, double-buffered so the in-flight wavefront's collective
        overlaps the resident wavefront's local chase
        (``make_pipelined_loop``), with fabric-side coordination collapsed
        to one stacked psum per superstep.

    All three produce bit-identical records, pool layouts, superstep counts,
    and wire accounting.  ``fabric="ring"`` swaps the dense all_to_all for
    ``lax.ppermute`` distance classes on any schedule (see ``_exchange``);
    ``local_backend="kernel"`` threads the device-resident local chase
    through the pulse_chase kernel's vectorized iterator body.

    ``fused=True`` runs the *entire* traversal as one device-resident
    program: the superstep loop becomes a ``lax.while_loop`` inside a single
    jitted ``shard_map`` executable (cached in ``_FUSED_CACHE``, pool buffer
    donated), with the local-vs-fabric decision taken on-device by a
    ``lax.cond`` around the all_to_all and the capacity ladder carried as
    traced state.  No host round-trip per hop: the host sees only the final
    pool plus aggregate counters, so ``RoutingStats`` carries totals instead
    of per-step lists.  Results are bit-identical to the dispatched schedule.
    Wire words stay the modeled ladder payload on both paths (see
    RoutingStats): the fused all_to_all buffer is fixed at base capacity
    (shapes cannot be traced), so the ladder's shrinkage is physical only
    when dispatched, while the local-only fabric skip is physical on both.

    ``compact=True`` enables active-set compaction of the supersteps:

      * finished records retire in place instead of being shipped home
        (``drain_done``), so only live traversals occupy link capacity;
      * the per-destination link capacity C adapts each superstep to a
        power-of-two envelope of the surviving active count, shrinking the
        all_to_all payload as the batch drains (a smaller C only parks
        overflow locally for one superstep -- correctness is unaffected);
      * supersteps where every active record already sits at its owning
        shard skip the all_to_all entirely (local-only fast path).

    Results are bit-identical to the uncompacted schedule (ptr/scratch/
    status/iters are scheduling-independent); only ``crossings`` differs,
    since finished records no longer hop home.  With ``return_to_cpu`` the
    home bounce IS the semantics being ablated (Fig. 9's crossings count),
    and both drain-in-place and the local-only/adaptive-capacity schedule
    would strand or delay exactly the hops that ablation measures -- so
    ``compact`` is ignored on that path.

    Returns (records (B, R) ordered by request id, RoutingStats) -- plus the
    post-commit ``Arena`` as a third element when ``it.mutates`` (the input
    arena object is left untouched, so the same pre-state can be replayed
    through several schedules and compared bit-for-bit).

    ``fault_injector`` (test-only, ``core.faults.FaultInjector``) threads an
    injected failure schedule through every schedule x fabric: a targeted
    kill raises ``ShardFailure`` *before* the named superstep executes (the
    input arena buffers are never mutated in place, so the observable heap
    stays at the pre-call state -- the recovery anchor), fabric loss parks
    and retransmits records under a seeded mask, and a straggler delay
    sleeps the dispatched host loop per superstep.

    ``elide_access_check=None`` (default) auto-specializes: when the
    iterator carries a pulse-verify certificate proving it read-only and
    every shard grants PERM_READ (``can_elide_access_check``), the per-hop
    protection probe compiles away -- bit-identical by construction, since
    the probe would have been constant True.  ``False`` forces the
    unspecialized path (the oracle for the bit-identity gate); ``True``
    asserts the caller's own proof and raises if the iterator mutates or
    replication is active.
    """
    kill_at = None
    delay_s = 0.0
    delay_shard = None
    drop_prob = 0.0
    drop_seed = 0
    if fault_injector is not None:
        call_idx = fault_injector.begin_call()
        kill_at = fault_injector.kill_step(call_idx)
        plan = fault_injector.plan
        drop_prob, drop_seed = float(plan.drop_prob), int(plan.drop_seed)
        if plan.delay_shard is not None:
            delay_s = float(plan.delay_s)
            delay_shard = int(plan.delay_shard)
    if schedule is None:
        schedule = "fused" if fused else "dispatched"
    if schedule not in ("dispatched", "fused", "pipelined"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if fabric not in ("dense", "ring"):
        raise ValueError(f"unknown fabric {fabric!r}")
    if local_backend not in ("xla", "kernel"):
        raise ValueError(f"unknown local_backend {local_backend!r}")
    mutate = it.mutates
    if mutate and return_to_cpu:
        raise ValueError(
            "mutating iterators cannot run under the return_to_cpu ablation: "
            "the home bounce would reorder commits against the write path's "
            "superstep contract"
        )
    if mutate and local_backend == "kernel":
        raise ValueError(
            "mutating iterators are not supported on the pulse_chase kernel "
            "local backend yet; use local_backend='xla'"
        )
    if replication is not None:
        if mutate:
            raise ValueError(
                "replication serves the READ path only: writes to a dead "
                "shard park under backoff until recovery rebuilds it"
            )
        if return_to_cpu:
            raise ValueError(
                "replication is incompatible with the return_to_cpu ablation"
            )
        if schedule in ("fused", "pipelined"):
            raise ValueError(
                "replication runs on the dispatched schedule (results are "
                "schedule-invariant, so degraded rounds fall back to it)"
            )
    if elide_access_check is None:
        # analysis-driven specialization: drop the per-hop PERM_READ probe
        # when the pulse-verify certificate + a host-side perms scan prove it
        # constant-true.  Replication rounds keep the probe: the replica path
        # carries its own primary-grant check and degraded-mode perms may
        # change between rounds.
        elide_access_check = replication is None and can_elide_access_check(
            it, arena
        )
    elif elide_access_check:
        if mutate or replication is not None:
            raise ValueError(
                "elide_access_check=True is only sound for verified "
                "read-only traversals without replication"
            )
    fused = schedule in ("fused", "pipelined")
    num_shards = arena.num_shards
    P_axis = mesh.shape[axis_name]
    if P_axis != num_shards:
        raise ValueError(f"arena has {num_shards} shards but mesh axis has {P_axis}")
    rows = arena.capacity
    if rows % num_shards:
        raise ValueError("distributed arena must have uniform shard sizes")

    B = ptr0.shape[0]
    Bp = ((B + num_shards - 1) // num_shards) * num_shards
    S = it.scratch_words
    MW = mut_width(arena.node_words) if mutate else 0
    ids = jnp.arange(B, dtype=jnp.int32)
    home = ids % num_shards
    rec = pack_requests(
        ids, home, jnp.asarray(ptr0, jnp.int32), jnp.asarray(scratch0, jnp.int32),
        mut_words=MW,
    )
    if Bp != B:
        rec = jnp.concatenate([rec, empty_records(Bp - B, S + MW)], axis=0)
        home_p = jnp.concatenate([home, jnp.arange(Bp - B, dtype=jnp.int32) % num_shards])
    else:
        home_p = home
    # place each request at its home shard; pool size L = Bp per shard is the
    # safe upper bound (all requests could, transiently, sit on one shard)
    L = Bp
    order = jnp.argsort(home_p, stable=True)
    rec_sorted = rec[order]
    counts = np.bincount(np.asarray(home_p), minlength=num_shards)
    pools = []
    off = 0
    for s in range(num_shards):
        c = int(counts[s])
        pools.append(
            jnp.concatenate(
                [rec_sorted[off : off + c], empty_records(L - c, S + MW)], axis=0
            )
        )
        off += c
    pool_global = jnp.stack(pools)  # (P, L, R)

    sharding = NamedSharding(mesh, P(axis_name))
    pool_global = jax.device_put(pool_global.reshape(num_shards * L, -1), sharding)
    if mutate:
        # no resident-arena cache on the write path: the arena is the value
        # being transformed, so place this call's snapshot explicitly (a
        # no-op when the caller chains the returned arena back in) and hand
        # the updated buffers back as a fresh Arena
        arena_data = jax.device_put(arena.data, NamedSharding(mesh, P(axis_name, None)))
        bounds = jax.device_put(arena.bounds, NamedSharding(mesh, P()))
        perms = jax.device_put(arena.perms, NamedSharding(mesh, P()))
        heap = jax.device_put(arena.heap, NamedSharding(mesh, P(axis_name, None)))
        commits0 = int(np.asarray(arena.heap)[:, H_COMMITS].sum())
        epochs0 = int(np.asarray(arena.heap)[:, H_EPOCH].sum())
    else:
        arena_data, bounds, perms = _resident_arena(arena, mesh, axis_name)

    base_capacity = L // num_shards
    compact = compact and not return_to_cpu
    drain_done = compact
    R = record_width(S, MW)

    if fused:
        runner = get_fused_runner(
            it, mesh, axis_name,
            num_shards=num_shards, pool_rows=num_shards * L, scratch_words=S,
            k_local=k_local, max_supersteps=max_supersteps,
            base_capacity=base_capacity, min_link_capacity=min_link_capacity,
            return_to_cpu=return_to_cpu, compact=compact,
            schedule=schedule, fabric=fabric, local_backend=local_backend,
            mutate=mutate, drop_prob=drop_prob, drop_seed=drop_seed,
            elide_access_check=elide_access_check,
        )
        # the quantum rides in as a traced operand: every budget value is a
        # cache hit on the same executable (int32 is safe -- callers cap
        # max_iters at 1 << 30)
        iter_budget = jnp.int32(min(max_iters, (1 << 31) - 1))
        # an armed kill caps the device loop at kill_superstep - 1 supersteps
        # via the traced halt operand; the unarmed value duplicates the
        # loop's own max_supersteps bound (same executable either way)
        halt = jnp.int32(kill_at - 1 if kill_at is not None else max_supersteps)
        if mutate:
            (pool_global, arena_data, heap, n_active, steps, n_routed, n_drop,
             cap_counts, local_only) = runner(
                pool_global, arena_data, heap, bounds, perms, iter_budget, halt
            )
        else:
            pool_global, n_active, steps, n_routed, n_drop, cap_counts, local_only = (
                runner(pool_global, arena_data, bounds, perms, iter_budget, halt)
            )
        if int(n_drop) != 0:  # not assert: must survive python -O
            raise RuntimeError(
                f"request records lost in routing (pool overflow): {int(n_drop)}"
            )
        if (
            kill_at is not None
            and int(n_active) > 0
            and int(steps) >= kill_at - 1
        ):
            # the loop halted at the injected death point with work left:
            # this call dies here, outputs discarded.  The input arena
            # buffers were never donated or mutated, so the caller's
            # observable state is exactly the pre-call snapshot.
            fault_injector.fire(kill_at)
        if int(n_active) != 0:
            raise RuntimeError(
                f"distributed_execute: {int(n_active)} records still ACTIVE after "
                f"max_supersteps={max_supersteps}; raise the cap or lower max_iters "
                f"(records would be returned with partial state otherwise)"
            )
        # decode the per-rung superstep histogram into a wire total with
        # Python integer arithmetic (exact at any batch size; a traced int32
        # product would wrap for production-scale pools)
        rungs = capacity_rungs(base_capacity, min_link_capacity) if compact else (
            base_capacity,
        )
        wire_total = sum(
            int(c) * num_shards * (num_shards - 1) * cap * R
            for c, cap in zip(np.asarray(cap_counts), rungs)
        )
        out = _decode_results(
            pool_global, B, S,
            mut_words=MW,
            supersteps=int(steps),
            local_only_steps=int(local_only),
            wire_words_total=wire_total,
            fused=True,
            schedule=schedule,
            fabric=fabric,
            num_shards=num_shards,
        )
        if mutate:
            heap_np = np.asarray(heap)
            out[1].commits = int(heap_np[:, H_COMMITS].sum()) - commits0
            out[1].epochs = int(heap_np[:, H_EPOCH].sum()) - epochs0
            new_arena = Arena(
                data=arena_data, bounds=arena.bounds, perms=arena.perms, heap=heap
            )
            return out[0], out[1], new_arena
        return out

    rep_plan = replication.plan if replication is not None else None

    def get_step(capacity: int | None, do_route: bool):
        # cached across calls: the serving loop re-enters distributed_execute
        # every scheduling round with identical parameters, and a per-call
        # cache would recompile the shard_map superstep each round
        key = (
            it, mesh, axis_name, num_shards, k_local, max_iters,
            return_to_cpu, drain_done, capacity, do_route, fabric,
            local_backend, mutate, drop_prob, drop_seed, rep_plan,
            elide_access_check,
        )
        if key not in _STEP_CACHE:
            CACHE_STATS.misses += 1
            superstep = make_superstep(
                it, num_shards, axis_name,
                k_local=k_local, max_iters=max_iters,
                return_to_cpu=return_to_cpu,
                link_capacity=capacity, drain_done=drain_done,
                do_route=do_route, fabric=fabric, local_backend=local_backend,
                mutate=mutate, drop_prob=drop_prob, drop_seed=drop_seed,
                replication=rep_plan,
                elide_access_check=elide_access_check,
            )
            # replication adds (holder-sharded replica rows, replicated
            # dead mask); fault-injected fabric loss adds one trailing
            # traced step_idx operand (the drop mask is keyed on it)
            rep_specs = (P(axis_name), P()) if rep_plan is not None else ()
            drop_specs = (P(),) if (drop_prob > 0.0 and do_route) else ()
            if mutate:
                in_specs = (
                    P(axis_name), P(axis_name), P(axis_name), P(), P(),
                ) + drop_specs
                out_specs = (
                    P(axis_name), P(axis_name), P(axis_name), P(), P(), P(), P(),
                )
            else:
                in_specs = (
                    (P(axis_name), P(axis_name), P(), P()) + rep_specs + drop_specs
                )
                out_specs = (P(axis_name), P(), P(), P(), P())
            # ISA-VM iterators run a lax.while_loop per step (the bounded
            # bytecode interpreter), which shard_map's replication checker
            # cannot analyze -- the check is off for those, exactly as the
            # fused/pipelined loops always run; traced iterators keep the
            # check as a free structural safety net.
            _STEP_CACHE[key] = jax.jit(
                shard_map(
                    superstep, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=not _is_vm_backed(it),
                )
            )
        else:
            CACHE_STATS.hits += 1
        return _STEP_CACHE[key]

    if replication is not None:
        rep_rows_dev = jax.device_put(
            jnp.asarray(replication.rep_rows, jnp.int32),
            NamedSharding(mesh, P(axis_name, None)),
        )
        dead_mask_dev = jax.device_put(
            jnp.asarray(replication.dead_mask, bool), NamedSharding(mesh, P())
        )
        rep_args = (rep_rows_dev, dead_mask_dev)
    else:
        rep_args = ()

    if delay_s > 0.0:
        _bnp = np.asarray(arena.bounds)
        _dlo, _dhi = int(_bnp[delay_shard]), int(_bnp[delay_shard + 1])

    routed_per_step = []
    active_per_step = []
    wire_words_per_step = []
    capacity_per_step = []
    local_only_steps = 0
    steps = 0
    # before the first superstep everything is active and sitting at home
    n_active, n_remote = B, B
    for _ in range(max_supersteps):
        # injected shard death: fires before the targeted (1-based) superstep
        # executes, so exactly kill_at - 1 supersteps of this call completed
        # and the caller's observable arena is the pre-call snapshot
        if kill_at is not None and steps + 1 >= kill_at:
            fault_injector.fire(steps + 1)
        if delay_s > 0.0:
            # attributable straggler: the slow memory node extends the BSP
            # barrier only on supersteps where it actually serves work (an
            # ACTIVE record pointing into its range).  Reads fanned out to
            # its replica cost it nothing -- which is what makes a per-shard
            # watchdog probe attributable: the probe to the straggler is
            # slow, probes elsewhere are not.
            serving = True
            if replication is not None and int(
                replication.plan.replica_map[delay_shard]
            ) >= 0:
                serving = not bool(np.asarray(replication.dead_mask)[delay_shard])
            if serving:
                pg = np.asarray(pool_global)
                act = pg[:, F_STATUS] == STATUS_ACTIVE
                ptrs = pg[:, F_PTR]
                serving = bool(np.any(act & (ptrs >= _dlo) & (ptrs < _dhi)))
            if serving:
                time.sleep(delay_s)
        if compact:
            # power-of-two envelope of the per-link demand; the ladder keeps
            # the number of distinct compiled supersteps at O(log L)
            demand = (int(n_active) + num_shards - 1) // num_shards
            capacity = min(
                base_capacity, max(min_link_capacity, _pow2_at_least(demand))
            )
            do_route = int(n_remote) > 0
        else:
            capacity, do_route = base_capacity, True
        # link_capacity is dead in the local-only step: collapse those cache
        # keys to one so the capacity ladder doesn't compile duplicate steps
        step_capacity = capacity if (compact and do_route) else None
        drop_args = (
            (jnp.int32(steps),) if (drop_prob > 0.0 and do_route) else ()
        )
        if mutate:
            (pool_global, arena_data, heap, n_active, n_routed, n_drop,
             n_remote) = get_step(step_capacity, do_route)(
                pool_global, arena_data, heap, bounds, perms, *drop_args
            )
        else:
            pool_global, n_active, n_routed, n_drop, n_remote = get_step(
                step_capacity, do_route
            )(pool_global, arena_data, bounds, perms, *rep_args, *drop_args)
        steps += 1
        routed_per_step.append(int(n_routed))
        active_per_step.append(int(n_active))
        capacity_per_step.append(capacity if do_route else 0)
        wire_words_per_step.append(
            num_shards * (num_shards - 1) * capacity * R if do_route else 0
        )
        local_only_steps += int(not do_route)
        if int(n_drop) != 0:  # not assert: must survive python -O
            raise RuntimeError(
                f"request records lost in routing (pool overflow): {int(n_drop)}"
            )
        if int(n_active) == 0:
            break
    else:
        raise RuntimeError(
            f"distributed_execute: {int(n_active)} records still ACTIVE after "
            f"max_supersteps={max_supersteps}; raise the cap or lower max_iters "
            f"(records would be returned with partial state otherwise)"
        )

    out = _decode_results(
        pool_global, B, S,
        mut_words=MW,
        supersteps=steps,
        routed_per_step=routed_per_step,
        active_per_step=active_per_step,
        wire_words_per_step=wire_words_per_step,
        capacity_per_step=capacity_per_step,
        local_only_steps=local_only_steps,
        schedule=schedule,
        fabric=fabric,
        num_shards=num_shards,
    )
    if mutate:
        heap_np = np.asarray(heap)
        out[1].commits = int(heap_np[:, H_COMMITS].sum()) - commits0
        out[1].epochs = int(heap_np[:, H_EPOCH].sum()) - epochs0
        new_arena = Arena(
            data=arena_data, bounds=arena.bounds, perms=arena.perms, heap=heap
        )
        return out[0], out[1], new_arena
    return out


def _decode_results(
    pool_global,
    B: int,
    scratch_words: int,
    *,
    mut_words: int = 0,
    supersteps: int,
    routed_per_step: list | None = None,
    active_per_step: list | None = None,
    wire_words_per_step: list | None = None,
    capacity_per_step: list | None = None,
    local_only_steps: int = 0,
    wire_words_total: int | None = None,
    fused: bool = False,
    schedule: str = "dispatched",
    fabric: str = "dense",
    num_shards: int = 0,
):
    """Gather the final pools, order records by request id, build stats."""
    all_rec = np.asarray(pool_global).reshape(
        -1, record_width(scratch_words, mut_words)
    )
    valid = all_rec[:, F_STATUS] != STATUS_EMPTY
    all_rec = all_rec[valid]
    all_rec = all_rec[all_rec[:, F_ID] < B]
    order = np.argsort(all_rec[:, F_ID], kind="stable")
    all_rec = all_rec[order]
    stats = RoutingStats(
        supersteps=supersteps,
        crossings=all_rec[:, F_HOPS].copy(),
        routed_per_step=routed_per_step or [],
        active_per_step=active_per_step or [],
        wire_words_per_step=wire_words_per_step or [],
        capacity_per_step=capacity_per_step or [],
        local_only_steps=local_only_steps,
        wire_words_total=wire_words_total,
        fused=fused,
        schedule=schedule,
        fabric=fabric,
        _num_shards=num_shards,
    )
    return all_rec, stats
