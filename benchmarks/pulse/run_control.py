#!/usr/bin/env python3
"""Run a cell with its control in the program's place, on several seeds in
one process, and print the numbers ``correct`` is decided on: the control
has to come out not correct on every seed.

  python3 benchmarks/pulse/run_control.py --workload <cell> --seeds 1,2,3 --seconds <s>

The control (``lib/control.py``) is the plain reference with one of the
configuration's guarantees broken, as ``cfg["control"]`` names.  Everything
else -- data from the seed, the arena on the chips, the traffic, the loop,
the read-back and the comparison -- is the benchmark's own.  Needs the
cell's chips, as ``bench.py`` does.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench  # noqa: E402
import registry  # noqa: E402


def control_hooks() -> bench.Hooks:
    from control import ControlService

    def factory(engine, built, cfg, struct, keys, values):
        return ControlService(cfg, built, struct, keys, values)

    return bench.Hooks(service_factory=factory)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    b = registry.load_benchmark()
    cell = registry.workload(b, args.workload)
    cfg = registry.config(b, cell["config"])
    mix = registry.traffic(cell["traffic"])
    sys.path.insert(0, str(bench.CHECKOUT / "src"))
    bench.setup_jax_cache()
    try:
        devices = bench.find_devices(int(cell["chips"]))
    except bench.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = bench.run_cell(
            b, cell, cfg, mix, seed=seed, seconds=args.seconds, trace=False,
            devices=devices, peak={}, hooks=control_hooks(),
        )
        res = out["result"]
        all_failed &= not res["correct"]
        print("control " + json.dumps({
            "workload": args.workload, "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "compared": res["compared"],
        }), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
