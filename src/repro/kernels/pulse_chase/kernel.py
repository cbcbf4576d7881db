"""pulse_chase: the PULSE accelerator as a Pallas TPU kernel (paper S4.2).

TPU-native adaptation of the disaggregated accelerator:

  * **memory pipelines**  -> async HBM->VMEM DMAs gathering one node record
    per in-flight lane (the single aggregated LOAD per iteration, S4.1).
    The arena stays in HBM (``pl.ANY``) and is only ever reached by
    ``make_async_copy``; only fetched records enter VMEM, mirroring "only
    fetched data crosses to the accelerator".
  * **logic pipelines**   -> the iterator body in lane-columnar form
    (``columns.lane_columns``) executing on the *previous* wave's records.
  * **m:n multiplexing**  -> software pipelining across WAVES of lanes:
    while wave ``g+1``'s records are in flight (DMA), wave ``g`` executes
    logic.  Property 1 (fetch->logic dependence *within* a lane) is
    respected; overlap comes only from independent lanes, exactly like the
    paper's scheduler (Fig. 4 bottom).

Layout.  Lane state lives in VMEM as ``(B, 1)`` int32 columns (pointer,
status, iteration count) plus the ``(B, S)`` scratch pads; the logic reads
and writes whole ``(wave, .)`` blocks of them.  DMA addresses are scalars,
so the kernel mirrors each lane's pointer into an SMEM array, plus one
"wave still has an active lane" flag per wave; both are refreshed from the
VMEM columns after every wave's logic.  The arena's record width must be a
multiple of 128 lanes, because Mosaic slices HBM rows only in whole
(8, 128) tiles along the minor dimension: ``ops.lane_pad`` zero-pads the
records (4- and 20-word nodes become 128-word rows; the iterator reads
only its own words).  ``wave`` must be a multiple of 8, the int32 sublane
tile, for the compiled kernel; interpret mode accepts any divisor of B.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NBUF = 2  # double buffering: one wave in flight per buffer slot
LANES = 128  # minor-dimension tile of an int32 array on TPU


def _chase_kernel(
    # inputs
    ptr_ref,  # (B, 1) int32 VMEM  current pointers
    scratch_ref,  # (B, S) int32 VMEM  scratch pads
    status_ref,  # (B, 1) int32 VMEM  0 active / 1 done
    iters_ref,  # (B, 1) int32 VMEM  per-lane iteration counts (accumulated)
    arena_ref,  # (cap, Wp) int32 in ANY/HBM -- the disaggregated heap
    # outputs (VMEM)
    out_ptr_ref,
    out_scratch_ref,
    out_status_ref,
    out_iters_ref,
    # scratch
    ptr_smem,  # (B,) int32 SMEM      scalar mirror of out_ptr (DMA addresses)
    live_smem,  # (num_waves,) SMEM   1 while a wave has an active lane
    node_buf,  # (NBUF, G, Wp) int32 VMEM -- landed node records
    copy_sem,  # (NBUF,) DMA semaphores
    *,
    columns_fn,
    num_steps: int,
    num_waves: int,
    wave: int,
):
    """Single-program kernel; waves of G lanes software-pipeline the DMAs."""
    G = wave
    cap = arena_ref.shape[0]

    out_ptr_ref[...] = ptr_ref[...]
    out_scratch_ref[...] = scratch_ref[...]
    out_status_ref[...] = status_ref[...]
    out_iters_ref[...] = iters_ref[...]

    def rows(g):
        return pl.ds(pl.multiple_of(g * G, G), G)

    def mirror(g):
        """Refresh wave g's SMEM pointers and liveness flag from VMEM."""
        ptr = out_ptr_ref[rows(g), :]
        for i in range(G):  # static: one scalar extract per lane
            ptr_smem[g * G + i] = ptr[i, 0]
        active = (out_status_ref[rows(g), :] == 0).astype(jnp.int32)
        live_smem[g] = jnp.max(active)

    def mirror_all(g, carry):
        mirror(g)
        return carry

    jax.lax.fori_loop(0, num_waves, mirror_all, 0)

    def copy(g, i, p):
        slot = jax.lax.rem(g, NBUF)
        return pltpu.make_async_copy(
            arena_ref.at[pl.ds(p, 1), :],
            node_buf.at[slot, pl.ds(i, 1), :],
            copy_sem.at[slot],
        )

    def issue_wave(g):
        """Memory pipeline: start DMAs for wave g's node records."""

        def one_lane(i, carry):
            p = jnp.clip(ptr_smem[g * G + i], 0, cap - 1)
            copy(g, i, p).start()
            return carry

        jax.lax.fori_loop(0, G, one_lane, 0)

    def wait_wave(g):
        def one_lane(i, carry):
            copy(g, i, 0).wait()
            return carry

        jax.lax.fori_loop(0, G, one_lane, 0)

    def logic_wave(g):
        """Logic pipeline: run the iterator body on wave g's landed records."""
        slot = jax.lax.rem(g, NBUF)
        nodes = node_buf[slot]  # (G, Wp)
        r = rows(g)
        ptr = out_ptr_ref[r, :]
        scr = out_scratch_ref[r, :]
        st = out_status_ref[r, :]
        itc = out_iters_ref[r, :]
        active = st == 0
        done, nptr, nscr = columns_fn(nodes, ptr, scr)
        ptr = jnp.where(active & ~done, nptr, ptr)
        scr = jnp.where(active, nscr, scr)
        st = jnp.where(active & done, 1, st)
        st = jnp.where((st == 0) & (ptr < 0), 1, st)
        # exact per-lane accounting: every step an active lane executes
        # counts -- including the step that discovers done (the XLA
        # executor's runnable-gated increment does the same)
        itc = jnp.where(active, itc + 1, itc)
        out_ptr_ref[r, :] = ptr.astype(jnp.int32)
        out_scratch_ref[r, :] = scr.astype(jnp.int32)
        out_status_ref[r, :] = st.astype(jnp.int32)
        out_iters_ref[r, :] = itc.astype(jnp.int32)
        mirror(g)

    def live(g):
        return live_smem[g] != 0

    def step(k, carry):
        # Every wave's fetch in step k uses the pointers its own logic wrote
        # in step k-1 (Property 1 per lane): wave g+1 is issued before wave
        # g's logic runs, and logic only rewrites its own wave.  A wave whose
        # lanes have all finished issues no DMAs and runs no logic (the
        # in-kernel half of the variable-depth wave scheduler;
        # ops.pulse_chase_waves compacts retired lanes out *between* kernel
        # invocations).  Issue and wait read the same flag, which only the
        # wave's own logic changes, so DMA semaphores stay balanced.
        @pl.when(live(0))
        def _():
            issue_wave(0)

        def pipelined(g, c):
            @pl.when(jnp.logical_and(g + 1 < num_waves, live(jnp.minimum(g + 1, num_waves - 1))))
            def _():
                issue_wave(g + 1)

            @pl.when(live(g))
            def _():
                wait_wave(g)
                logic_wave(g)

            return c

        jax.lax.fori_loop(0, num_waves, pipelined, 0)
        return carry

    jax.lax.fori_loop(0, num_steps, step, 0)


def pulse_chase_pallas(
    arena: jax.Array,  # (cap, Wp) int32, Wp a multiple of 128
    ptr: jax.Array,  # (B,) int32
    scratch: jax.Array,  # (B, S)
    status: jax.Array,  # (B,)
    iters: jax.Array,  # (B,) int32 -- accumulated; returned exact per-lane
    *,
    columns_fn,
    num_steps: int,
    wave: int = 8,
    interpret: bool = False,
):
    B = ptr.shape[0]
    if B % wave:
        raise ValueError(f"batch {B} must be a multiple of wave size {wave}")
    Wp = arena.shape[1]
    if Wp % LANES:
        raise ValueError(
            f"arena record width {Wp} must be a multiple of {LANES} lanes "
            "(pad it with ops.lane_pad)"
        )
    num_waves = B // wave
    S = scratch.shape[1]
    kernel = functools.partial(
        _chase_kernel,
        columns_fn=columns_fn,
        num_steps=num_steps,
        num_waves=num_waves,
        wave=wave,
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    col = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    p, s, st, it = pl.pallas_call(
        kernel,
        in_specs=[vmem, vmem, vmem, vmem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[vmem, vmem, vmem, vmem],
        out_shape=[col, jax.ShapeDtypeStruct((B, S), jnp.int32), col, col],
        scratch_shapes=[
            pltpu.SMEM((B,), jnp.int32),
            pltpu.SMEM((num_waves,), jnp.int32),
            pltpu.VMEM((NBUF, wave, Wp), jnp.int32),
            pltpu.SemaphoreType.DMA((NBUF,)),
        ],
        interpret=interpret,
        name="pulse_chase",
    )(
        ptr.reshape(B, 1),
        scratch,
        status.reshape(B, 1),
        iters.reshape(B, 1),
        arena,
    )
    return p.reshape(B), s, st.reshape(B), it.reshape(B)
