"""CPU rehearsal of ``chip_smoke.py``: its phases and checks at a tiny size.

The script itself refuses to run without a TPU; these tests drive the same
phase functions on the CPU (the kernel in interpret mode, four virtual
devices for the sharded path) so a broken smoke is caught before it costs
chip time.
"""

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


def _tiny_data(smoke, seed=0):
    from repro.configs import pulse_paper

    ws = dataclasses.replace(pulse_paper.WEBSERVICE, n_keys=3000, n_buckets=64)
    wt = dataclasses.replace(pulse_paper.WIREDTIGER, n_keys=5000)
    return smoke.make_data(seed, ws, wt)


def test_chip_smoke_refuses_without_tpu(capsys):
    smoke = _load_smoke()
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""  # no result line at all
    assert "no TPU" in out.err


def test_chip_smoke_one_chip_phases_on_cpu():
    smoke = _load_smoke()
    recs = {r["phase"]: r for r in smoke.run_one_chip(_tiny_data(smoke), n_reads=96, n_mix=64)}
    assert list(recs) == ["read_xla", "update_mix", "readback", "read_kernel"]
    for r in recs.values():
        assert r["mismatches"] == 0, r
        assert r["cpu_node_quanta"] == 0, r
    assert set(recs["read_xla"]["quanta_by_path"]) == {"xla"}
    assert set(recs["update_mix"]["quanta_by_path"]) == {"xla", "commit_oracle"}
    assert set(recs["read_kernel"]["quanta_by_path"]) == {"kernel"}
    assert recs["readback"]["requests"] > 0


def test_chip_smoke_detects_a_wrong_answer():
    """The check is live: a reference that disagrees with the arena fails."""
    smoke = _load_smoke()
    data = _tiny_data(smoke)
    arena, heads, root, _ = smoke.build_arena(data, 1)
    data.bvals[0] ^= 1  # the dict now disagrees with the arena on one key
    from repro.core.engine import PulseEngine
    from repro.serving.admission import TraversalRequest
    from repro.serving.traversal_service import PulseService

    svc = PulseService(PulseEngine(arena), smoke.specs(heads, root, data.n_buckets, writes=False))
    reqs = [
        TraversalRequest(req_id=i, structure="btree", query=int(k), tenant="ycsb")
        for i, k in enumerate(data.bkeys[:4])
    ]
    rec = smoke.serve("read_xla", svc, reqs, data.reference(), warm=1)
    assert rec["mismatches"] == 1


def test_chip_smoke_sees_the_order_of_updates():
    """Updates of one key served in one run apply in submission order: the
    dict and the in-order oracle replay agree with the service, and both
    checks fail for the same updates with two of them swapped."""
    smoke = _load_smoke()
    data = _tiny_data(smoke)
    arena, heads, root, _ = smoke.build_arena(data, 1)
    from repro.core.engine import PulseEngine
    from repro.serving.admission import TraversalRequest
    from repro.serving.traversal_service import PulseService

    engine = PulseEngine(arena)
    svc = PulseService(engine, smoke.specs(heads, root, data.n_buckets, writes=True))
    k, other = int(data.bkeys[0]), int(data.bkeys[1])
    reqs = [
        TraversalRequest(i, "btree_update", q, value=smoke.new_value(i, data.seed), tenant="ycsb")
        for i, q in enumerate([k, other, k, k])
    ]
    reqs.append(TraversalRequest(4, "btree", k, tenant="ycsb"))
    rec = smoke.serve("update_mix", svc, reqs, data.reference(), warm=0)
    assert rec["mismatches"] == 0, rec
    final = np.asarray(engine.arena.data)
    assert np.array_equal(np.asarray(smoke.replay_updates(arena, root, reqs).data), final)

    swapped = [reqs[0], reqs[1], reqs[3], reqs[2], reqs[4]]
    assert smoke.mismatches(reqs, smoke.expected(swapped, data.reference()))
    assert not np.array_equal(
        np.asarray(smoke.replay_updates(arena, root, swapped).data), final
    )


def test_chip_smoke_sharded_phases_on_cpu():
    """The --chips 4 path on four virtual CPU devices (a subprocess, since
    the device count is fixed when JAX starts)."""
    code = (
        "import dataclasses, importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('chip_smoke', {str(ROOT / 'chip_smoke.py')!r})\n"
        "m = importlib.util.module_from_spec(spec); sys.modules['chip_smoke'] = m\n"
        "spec.loader.exec_module(m)\n"
        "from repro.configs import pulse_paper\n"
        "ws = dataclasses.replace(pulse_paper.WEBSERVICE, n_keys=3000, n_buckets=64)\n"
        "wt = dataclasses.replace(pulse_paper.WIREDTIGER, n_keys=5000)\n"
        "recs = m.run_four_chips(m.make_data(0, ws, wt), n_reads=64, n_mix=48)\n"
        "bad = [r['phase'] for r in recs if r['mismatches'] or r.get('cpu_node_quanta')]\n"
        "o = recs[-1]\n"
        "assert not bad and o['final_arena_equal'] and o['distinct_shard_devices'] == 4, recs\n"
        "print('SHARDED_OK')\n"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "SHARDED_OK" in proc.stdout
