"""Drive whole runs of the benchmark on the CPU at a tiny size, with the
harness's look for a chip skipped, and print what each run decided.

  JAX_PLATFORMS=cpu python3 benchmarks/pulse/tests/scenarios.py <cell> <scenario> ...

A scenario is ``sound`` (the program as it is), ``control`` (the reference
with a guarantee broken, ``lib/control.py``) or a fault planted under the
timed path by wrapping the engine's ``execute``:

  state_unchanged    every quantum returns its input state as finished, and
                     a write quantum commits nothing
  half_batch         the second half of each quantum's occupied lanes is left
                     out: returned as its input state, finished
  answer_altered     the first answered find of each quantum is given another
                     value
  exchange_left_out  a lane whose chase crossed to another memory node in the
                     quantum answers not-found, as if the exchange had not run

A cell is a name from ``BENCHMARK.json``; ``<cell>@<n>`` runs it with its
arena range-partitioned over ``n`` (virtual) chips, one shard per chip, for
the faults that only a sharded arena can have.  The last line of output is a
JSON object: scenario -> the run's result.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

# four virtual CPU devices, for the cells that hold four chips
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import bench  # noqa: E402
import registry  # noqa: E402

TINY = {"hash_table": {"n_keys": 4000, "n_buckets": 82}}
OPEN_RATE = 100.0
SECONDS = 1.5


def _occupied(ptr0):
    from repro.core.arena import NULL

    return np.flatnonzero(np.asarray(ptr0) != NULL)


def _wrap(fault: str):
    from repro.core.engine import ExecResult
    from repro.core.iterator import STATUS_DONE

    def install(engine):
        inner = engine.execute

        def execute(it, ptr0, scratch0, **kw):
            ptr0 = np.asarray(ptr0, np.int32)
            scratch0 = np.asarray(scratch0, np.int32).reshape(len(ptr0), -1)
            if fault == "state_unchanged":
                return ExecResult(ptr0.copy(), scratch0.copy(),
                                  np.full(len(ptr0), STATUS_DONE, np.int32),
                                  np.zeros(len(ptr0), np.int32))
            res = inner(it, ptr0, scratch0, **kw)
            ptr = np.array(res.ptr)
            scr = np.array(res.scratch)
            status = np.array(res.status)
            occ = _occupied(ptr0)
            if fault == "half_batch":
                out = occ[len(occ) // 2:]
                ptr[out], scr[out], status[out] = ptr0[out], scratch0[out], STATUS_DONE
            elif fault == "answer_altered" and not it.mutates:
                hit = [s for s in occ if status[s] == STATUS_DONE and scr[s, 2] == 1]
                if hit:
                    scr[hit[0], 1] += 1
            elif fault == "exchange_left_out" and not it.mutates:
                crossed = occ[np.asarray(res.stats.crossings)[occ] > 0]
                scr[crossed, 1], scr[crossed, 2] = -(2**31) + 1, 0
                status[crossed] = STATUS_DONE
            return ExecResult(ptr, scr, status, res.iters, res.stats,
                              res.offloaded, res.arena, res.path)

        engine.execute = execute

    return install


def run(cell_name: str, scenario: str) -> dict:
    import jax

    b = registry.load_benchmark()
    cell_name, _, shards = cell_name.partition("@")
    cell = dict(registry.workload(b, cell_name))
    cfg = dict(registry.config(b, cell["config"]))
    if shards:
        cfg["shards"] = cfg["chips"] = cell["chips"] = int(shards)
    cfg.update(TINY[cfg["structure"]])
    mix = dict(registry.traffic(cell["traffic"]))
    if mix["loop"] == "open":
        mix["rate_rps"] = OPEN_RATE
    if scenario == "sound":
        hooks = None
    elif scenario == "control":
        import run_control

        hooks = run_control.control_hooks()
    else:
        hooks = bench.Hooks(wrap_execute=_wrap(scenario))
    devices = jax.devices()[: cell["chips"]]
    out = bench.run_cell(b, cell, cfg, mix, seed=2**31 + 17, seconds=SECONDS,
                         trace=False, devices=devices, peak={}, hooks=hooks)
    return out["result"]


def main(argv) -> int:
    bench.setup_jax_cache()
    cell, scenarios = argv[0], argv[1:]
    print(json.dumps({s: run(cell, s) for s in scenarios}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
