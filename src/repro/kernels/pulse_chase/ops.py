"""jit'd wrapper for the pulse_chase kernel + PulseIterator adapter."""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.iterator import PulseIterator
from repro.core.routing import ExecutableCacheStats
from repro.kernels.pulse_chase.columns import lane_columns
from repro.kernels.pulse_chase.kernel import LANES, pulse_chase_pallas
from repro.kernels.pulse_chase.ref import chase_reference

# Executable reuse accounting for the kernel backend (same discipline as the
# routing layer's fused cache): ``traces`` only moves when a new (shape,
# statics) combination forces a recompile, so the wave scheduler's pow2 lane
# ladder is regression-tested to stay at O(log B) compiles across waves.
CACHE_STATS = ExecutableCacheStats()


def iterator_logic(it: PulseIterator):
    """Vectorized fused next+end body for a PulseIterator (the compiled
    iterator the dispatch engine ships to the accelerator).

    ``logic(nodes (B, W), ptr (B,), scratch (B, S)) -> (done, ptr,
    scratch)`` is the ``vmap`` of the per-lane body, which XLA compiles (the
    reference and the distributed local chase run it).  ``logic.columns`` is
    the same body in lane-columnar form on ``(B, 1)`` pointer columns, the
    form Mosaic compiles (``columns.lane_columns``): the kernel runs it, so
    kernel-vs-reference tests check one form against the other."""

    def one(node, ptr, scratch):
        if it.step_fn is not None:
            return it.step_fn(node, ptr, scratch)
        done, scr = it.end_fn(node, ptr, scratch)
        nptr, nscr = it.next_fn(node, ptr, scr)
        return done, jnp.where(done, ptr, nptr), jnp.where(done, scr, nscr)

    def logic(nodes, ptr, scratch):
        done, nptr, nscr = jax.vmap(one)(nodes, ptr, scratch)
        return done, nptr.astype(jnp.int32), nscr.astype(jnp.int32)

    logic.columns = lane_columns(one)
    return logic


def check_lane_columns(it: PulseIterator, node_words: int) -> None:
    """Trace ``it``'s body in lane-columnar form at the kernel's padded
    record width; raises ``NotImplementedError`` naming what the compiled
    kernel cannot run (``columns``)."""
    wave, width = 8, -(-node_words // LANES) * LANES
    jax.eval_shape(
        iterator_logic(it).columns,
        jax.ShapeDtypeStruct((wave, width), jnp.int32),
        jax.ShapeDtypeStruct((wave, 1), jnp.int32),
        jax.ShapeDtypeStruct((wave, it.scratch_words), jnp.int32),
    )


# the last lane-padded arena, as (source buffer, padded copy)
_PADDED: tuple = (None, None)


def lane_pad(arena_data: jax.Array) -> jax.Array:
    """Zero-pad node records to a multiple of 128 int32 lanes: the compiled
    kernel DMAs whole rows, and Mosaic slices HBM rows only in whole lane
    tiles.  Padding words are never read by an iterator.

    The padded copy of the last device arena is kept, keyed by the identity
    of its (immutable) buffer: a resident arena is padded once, and a write
    that swaps the arena's buffer is padded anew on its next read."""
    global _PADDED
    W = arena_data.shape[1]
    if W % LANES == 0:
        return arena_data
    src, padded = _PADDED
    if src is arena_data:
        return padded
    padded = _pad_lanes(arena_data, -(-W // LANES) * LANES)
    if isinstance(arena_data, jax.Array) and not isinstance(arena_data, jax.core.Tracer):
        _PADDED = (arena_data, padded)
    return padded


@partial(jax.jit, static_argnums=1)
def _pad_lanes(arena_data: jax.Array, width: int) -> jax.Array:
    pad = width - arena_data.shape[1]
    return jnp.pad(jnp.asarray(arena_data, jnp.int32), ((0, 0), (0, pad)))


@partial(
    jax.jit,
    static_argnames=("logic_fn", "num_steps", "wave", "interpret", "use_pallas"),
    donate_argnames=("ptr", "scratch", "status", "iters"),
)
def _pulse_chase_donated(
    arena_data: jax.Array,
    ptr: jax.Array,
    scratch: jax.Array,
    status: jax.Array,
    iters: jax.Array,
    *,
    logic_fn,
    num_steps: int,
    wave: int = 8,
    interpret: bool = False,
    use_pallas: bool = True,
):
    """The one compiled executable behind both entry points.

    Lane buffers (ptr/scratch/status/iters) are donated: the wave scheduler
    owns its padded buffers and rebuilds them per chunk, so XLA may alias
    them in place.  The arena is never donated -- it is the resident state
    reused across waves.  Callers that do not own their buffers go through
    ``pulse_chase``, which copies first.
    """
    CACHE_STATS.traces += 1  # trace-time side effect: counts recompiles
    ptr = jnp.asarray(ptr, jnp.int32)
    scratch = jnp.asarray(scratch, jnp.int32)
    status = jnp.asarray(status, jnp.int32)
    iters = jnp.asarray(iters, jnp.int32)
    if not use_pallas:
        return chase_reference(
            arena_data, ptr, scratch, status, iters, logic_fn, num_steps
        )
    return pulse_chase_pallas(
        arena_data,
        ptr,
        scratch,
        status,
        iters,
        columns_fn=logic_fn.columns,
        num_steps=num_steps,
        wave=wave,
        interpret=interpret,
    )


def pulse_chase(
    arena_data: jax.Array,
    ptr: jax.Array,
    scratch: jax.Array,
    status: jax.Array,
    iters: jax.Array | None = None,
    *,
    logic_fn,
    num_steps: int,
    wave: int = 8,
    interpret: bool = False,
    use_pallas: bool = True,
):
    """Run ``num_steps`` traversal iterations for a batch of lanes.

    Returns ``(ptr, scratch, status, iters)`` -- ``iters`` is the exact
    per-lane iteration count (accumulated on top of the passed-in counts,
    zeros when omitted): every step an active lane executes counts,
    including the step that discovers done, matching the XLA executor's
    runnable-gated accounting bit-for-bit.

    ``use_pallas=False`` runs the pure-jnp reference instead of the kernel;
    ``interpret=True`` runs the Pallas kernel body in interpret mode (CPU
    validation of the TPU kernel).  The default compiles the kernel, which
    needs a TPU.

    The caller's lane buffers are copied (``jnp.array``) before entering the
    donating executable, so they stay valid after the call.
    """
    if iters is None:
        iters = jnp.zeros(jnp.asarray(ptr).shape, jnp.int32)
    return _pulse_chase_donated(
        lane_pad(arena_data) if use_pallas else arena_data,
        jnp.array(ptr, jnp.int32),
        jnp.array(scratch, jnp.int32),
        jnp.array(status, jnp.int32),
        jnp.array(iters, jnp.int32),
        logic_fn=logic_fn,
        num_steps=num_steps,
        wave=wave,
        interpret=interpret,
        use_pallas=use_pallas,
    )


# ------------------------- variable-depth scheduling -------------------------


@dataclasses.dataclass
class WaveStats:
    """Accounting for the variable-depth wave scheduler.

    ``lane_steps`` is the work actually executed (surviving+padding lanes x
    steps, summed over chunks); ``dense_lane_steps`` is what the fixed-depth
    scheduler would have executed (every lane runs every step).  The ratio is
    the fraction of accelerator issue slots the early-retire scheduler saved.
    """

    chunks: int = 0
    lane_steps: int = 0
    dense_lane_steps: int = 0
    steps_per_chunk: list = dataclasses.field(default_factory=list)
    lanes_per_chunk: list = dataclasses.field(default_factory=list)
    retire_step: np.ndarray | None = None  # (B,) EXACT per-lane iteration
    # count at retirement (0 for NULL-entry lanes; the executed count for
    # lanes that never finished), accumulated by the kernel itself -- wave
    # retirement no longer rounds it up to the chunk boundary, so downstream
    # hop accounting (ServiceMetrics.lane_iters, ExecResult.iters) is exact
    faulted: np.ndarray | None = None  # (B,) lanes retired by fault_fn
    # (or by a NULL/negative pointer) rather than by finishing

    @property
    def savings(self) -> float:
        if not self.dense_lane_steps:
            return 0.0
        return 1.0 - self.lane_steps / self.dense_lane_steps


def _pad_ladder(n: int, wave: int) -> int:
    """Smallest wave multiple >= n from the power-of-two ladder {wave, 2*wave,
    4*wave, ...} -- bounds the number of distinct compiled batch shapes at
    O(log B) while keeping padding overhead under 2x."""
    m = wave
    while m < n:
        m *= 2
    return m


def pulse_chase_waves(
    arena_data: jax.Array,
    ptr: jax.Array,
    scratch: jax.Array,
    status: jax.Array,
    *,
    logic_fn,
    max_steps: int,
    depth_quantum: int = 8,
    wave: int = 8,
    interpret: bool = False,
    use_pallas: bool = True,
    fault_fn=None,
):
    """Variable-depth traversal: retire finished lanes between depth quanta.

    The fixed-depth ``pulse_chase`` runs every lane for ``num_steps``
    iterations even after it finishes -- fine when depths are uniform (B-tree
    descent), wasteful for skewed workloads (hash chains, list walks) where a
    few deep lanes pin the depth for everyone.  This scheduler runs the
    kernel in chunks of ``depth_quantum`` steps, pulls lane status between
    chunks, compacts retired lanes out of the batch (pow2 ladder padding so
    recompiles stay bounded), and keeps only survivors in flight -- the m:n
    multiplexer only ever holds live traversals, mirroring the routing
    layer's active-set compaction.

    Lanes entering with ``ptr == NULL`` retire immediately with their init
    scratch (the executor's FAULT-on-NULL semantics, minus the status code --
    the caller maps status if it needs to distinguish).

    ``fault_fn`` is the translation/protection layer's hook: a host-side
    ``(ptrs int32 array) -> bool mask`` applied to live lanes on entry and
    between chunks; ``True`` lanes retire as faults (``stats.faulted``).
    Fault detection is therefore quantum-granular -- a lane stepping into a
    bad range mid-chunk executes up to ``depth_quantum - 1`` extra (clamped,
    harmless) loads before it is retired.

    Returns ``(ptr, scratch, status, stats)`` in the original lane order;
    results are identical to running the fixed scheduler for ``max_steps``.
    """
    if use_pallas:
        arena_data = lane_pad(arena_data)  # once, not per depth quantum
    out_ptr = np.asarray(ptr, np.int32).copy()
    out_scr = np.asarray(scratch, np.int32).copy()
    out_st = np.asarray(status, np.int32).copy()
    B = out_ptr.shape[0]
    out_it = np.zeros(B, np.int32)  # exact per-lane counts from the kernel
    faulted = np.zeros(B, bool)
    faulted[(out_st == 0) & (out_ptr < 0)] = True  # NULL entry: fault on arrival

    stats = WaveStats(dense_lane_steps=B * max_steps)
    stats.retire_step = out_it
    stats.faulted = faulted

    def _apply_faults(idx):
        """Retire live lanes whose pointer fails the caller's check."""
        if fault_fn is None or not idx.size:
            return idx
        bad = np.asarray(fault_fn(out_ptr[idx]), bool)
        faulted[idx[bad]] = True
        out_st[idx[bad]] = 1
        return idx[~bad]

    out_st[faulted] = 1
    steps_done = 0
    live = _apply_faults(np.flatnonzero(out_st == 0))
    while live.size and steps_done < max_steps:
        q = min(depth_quantum, max_steps - steps_done)
        n = int(live.size)
        padded = _pad_ladder(n, wave)
        p_in = np.full(padded, -1, np.int32)
        s_in = np.zeros((padded, out_scr.shape[1]), np.int32)
        st_in = np.ones(padded, np.int32)  # padding lanes are born retired
        it_in = np.zeros(padded, np.int32)
        p_in[:n] = out_ptr[live]
        s_in[:n] = out_scr[live]
        st_in[:n] = 0
        it_in[:n] = out_it[live]  # kernel accumulates on top: counts stay exact
        # chunk buffers are freshly built above, so hand them straight to the
        # donating executable (no defensive copy); the pow2 lane ladder keeps
        # the executable cache at O(log B) entries across waves
        p1, s1, st1, it1 = _pulse_chase_donated(
            arena_data,
            jnp.asarray(p_in),
            jnp.asarray(s_in),
            jnp.asarray(st_in),
            jnp.asarray(it_in),
            logic_fn=logic_fn,
            num_steps=q,
            wave=wave,
            interpret=interpret,
            use_pallas=use_pallas,
        )
        out_ptr[live] = np.asarray(p1)[:n]
        out_scr[live] = np.asarray(s1)[:n]
        out_st[live] = np.asarray(st1)[:n]
        out_it[live] = np.asarray(it1)[:n]
        steps_done += q
        stats.chunks += 1
        stats.lane_steps += padded * q
        stats.steps_per_chunk.append(q)
        stats.lanes_per_chunk.append(n)
        # lanes the kernel retired on a negative pointer are faults too
        faulted[live[(np.asarray(st1)[:n] == 1) & (np.asarray(p1)[:n] < 0)]] = True
        live = _apply_faults(live[np.asarray(st1)[:n] == 0])
    return out_ptr, out_scr, out_st, stats
