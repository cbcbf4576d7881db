"""Fused device-resident routing: retrace/donation regressions (multi-device
checks run subprocess-isolated; executable-reuse checks for the kernel and
local engine paths run in-process on the single default device)."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax.numpy as jnp

from repro.core.engine import PulseEngine
from repro.core.iterator import STATUS_DONE
from repro.core.structures import hash_table, linked_list

ROOT = Path(__file__).resolve().parents[1]
RNG = np.random.default_rng(31)


def test_fused_routing_subprocess():
    """Retracing + donation + resident-arena checks need >1 XLA device, so
    they run in a subprocess with their own XLA_FLAGS (same isolation rule as
    test_distributed_routing)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "helpers" / "fused_checks.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL FUSED CHECKS PASSED" in proc.stdout


def test_kernel_wave_executables_reused_across_waves():
    """A second identical wave-scheduled run must be all cache hits: the
    donating pulse_chase executable retraces zero times."""
    from repro.kernels.pulse_chase import ops

    keys = RNG.choice(np.arange(10**5), size=128, replace=False).astype(np.int32)
    values = RNG.integers(0, 10**6, 128).astype(np.int32)
    ar, heads = hash_table.build(keys, values, 8)
    it = hash_table.find_iterator(8)
    q = np.concatenate([keys[:24], RNG.integers(10**5, 10**6, 8).astype(np.int32)])
    ptr0, scr0 = it.init(jnp.asarray(q), jnp.asarray(heads))
    logic = ops.iterator_logic(it)

    first = ops.pulse_chase_waves(
        ar.data, ptr0, scr0, np.zeros(32, np.int32),
        logic_fn=logic, max_steps=64, depth_quantum=8, wave=8, interpret=True,
    )
    assert first[3].chunks > 1  # the schedule actually spans several waves
    ops.CACHE_STATS.reset()
    second = ops.pulse_chase_waves(
        ar.data, ptr0, scr0, np.zeros(32, np.int32),
        logic_fn=logic, max_steps=64, depth_quantum=8, wave=8, interpret=True,
    )
    assert ops.CACHE_STATS.traces == 0, ops.CACHE_STATS
    np.testing.assert_array_equal(first[0], second[0])
    np.testing.assert_array_equal(first[1], second[1])


def test_pulse_chase_public_wrapper_preserves_caller_buffers():
    """ops.pulse_chase donates internally but copies first: the caller's
    arrays must survive the call and be reusable."""
    from repro.kernels.pulse_chase import ops

    keys = np.arange(32, dtype=np.int32)
    ar, head = linked_list.build(keys, keys * 3)
    it = linked_list.find_iterator()
    ptr0, scr0 = it.init(jnp.asarray(keys[:8]), head)
    st0 = jnp.zeros(8, jnp.int32)
    logic = ops.iterator_logic(it)
    r1 = ops.pulse_chase(
        ar.data, ptr0, scr0, st0, logic_fn=logic, num_steps=40, interpret=True
    )
    assert not ptr0.is_deleted() and not scr0.is_deleted() and not st0.is_deleted()
    r2 = ops.pulse_chase(
        ar.data, ptr0, scr0, st0, logic_fn=logic, num_steps=40, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(r1[1]), np.asarray(r2[1]))


def test_engine_local_path_caches_and_preserves_inputs():
    """Repeated same-shaped local executes reuse one compiled executable
    (donating copies, so the caller's arrays stay alive)."""
    keys = np.arange(64, dtype=np.int32)
    values = RNG.integers(0, 10**6, 64).astype(np.int32)
    ar, head = linked_list.build(keys, values)
    it = linked_list.find_iterator()
    eng = PulseEngine(ar)
    ptr0, scr0 = it.init(jnp.asarray(keys[:16]), head)
    r1 = eng.execute(it, ptr0, scr0, max_iters=256)
    assert not ptr0.is_deleted() and not scr0.is_deleted()
    r2 = eng.execute(it, ptr0, scr0, max_iters=256)
    assert len(eng._local_jit) == 1
    np.testing.assert_array_equal(r1.scratch, r2.scratch)
    assert (r1.status == STATUS_DONE).all()
