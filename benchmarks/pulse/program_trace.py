#!/usr/bin/env python3
"""Run a cell as ``bench.py`` does, with the program's own tracer
(``repro.core.trace.TRACE``) on over the measured window, and print what its
spans and its counter read.

  python3 benchmarks/pulse/program_trace.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything else is the benchmark's: data from the seed, the service, the
warm-up, the loop, the read-back, the comparison and the lines ``bench.py``
prints, the result line among them (its ``attempted`` is the requests the
window took, to set against an untraced run of the same seed).  The tracer
is started when the window opens and stopped when it closes, as the
benchmark's own spans are.  Before the benchmark's lines it prints

  program_metrics       each reader of ``metrics/`` that reads the program's
                        spans (``lib/program_spans.py``), by name
  consistency           the program's round self time against the
                        benchmark's ``service_self_ms_per_round`` (``--trace 1``)
                        and the share of ``engine.execute`` its children cover
  idle_by_program_span  device idle seconds of the window by the innermost
                        ``pulse.*`` span open over them (``--trace 1``)

``--trace 0`` keeps the JAX profiler off, so the run shows what the tracer
alone costs; the readers of the device trace then print nothing.

Exits 2 where ``bench.py`` would, or where the program has no tracer.
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench  # noqa: E402
import registry  # noqa: E402

READERS = (
    "admit_self_ms_per_round.tput",
    "init_ms_per_round.tput",
    "retire_ms_per_round.tput",
    "dispatch_ms_per_quantum.tput",
    "device_wait_ms_per_quantum.tput",
    "host_syncs_per_quantum.tput",
    "eager_programs_per_round.tput",
)


def traced_run(b: dict, cell: dict, cfg: dict, mix: dict, *, seed: int, seconds: float,
               trace: bool, devices: list, peak: dict, hooks=None) -> dict:
    """``bench.run_cell`` with the program's tracer on over the window.
    Returns its output plus ``program`` (the tracer's records) and
    ``trace`` (the reduced device trace with the ``pulse.*`` host events
    under ``program``; None without ``trace``)."""
    import devtrace
    import program_spans
    from repro.core.trace import TRACE

    got: dict = {}
    inner_loop, inner_load = bench.run_loop, devtrace.load_events

    def run_loop(client, gen, mix, seconds, rng, on_close=None):
        if on_close is None:  # a warm-up pass
            return inner_loop(client, gen, mix, seconds, rng)

        def close():
            got["records"] = TRACE.stop()
            on_close()

        TRACE.start()
        return inner_loop(client, gen, mix, seconds, rng, close)

    def load_events(path):
        got["events"] = program_spans.load_events(path)
        got["trace"] = inner_load(path)
        return got["trace"]

    bench.run_loop, devtrace.load_events = run_loop, load_events
    try:
        out = bench.run_cell(b, cell, cfg, mix, seed=seed, seconds=seconds,
                             trace=trace, devices=devices, peak=peak, hooks=hooks)
    finally:
        bench.run_loop, devtrace.load_events = inner_loop, inner_load
        TRACE.stop()
    reduced = None
    if "trace" in got:
        ids = [d.id for d in devices[: int(cfg["shards"])]]
        reduced = {**devtrace.only_devices(got["trace"], ids), "program": got["events"]}
    return {**out, "program": got.get("records"), "trace": reduced}


def readings(out: dict) -> dict:
    """The program's readers, the consistency checks and the idle split."""
    import program_spans

    view = types.SimpleNamespace(program=out["program"], trace=out["trace"])
    metrics = {}
    for name in READERS:
        v = registry.metric_module(name).read(view)
        if v is not None:
            metrics[name] = float(v)
    self_ms = out["result"]["metrics"].get("service_self_ms_per_round.tput", {}).get("value")
    return {
        "program_metrics": metrics,
        "consistency": program_spans.consistency(out["program"] or [], self_ms),
        "idle_by_program_span": program_spans.idle_by_program_span(out["trace"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    try:
        b = registry.load_benchmark()
        cell = registry.workload(b, args.workload)
        cfg = registry.config(b, cell["config"])
        mix = registry.traffic(cell["traffic"])
    except registry.LookupFailed as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(bench.CHECKOUT / "src"))
    try:
        import repro.core.trace  # noqa: F401
    except ImportError:
        print("program_trace: the program has no tracer (repro.core.trace)", file=sys.stderr)
        return 2
    bench.setup_jax_cache()
    try:
        devices = bench.find_devices(int(cell["chips"]))
        peak = registry.peaks(devices[0].device_kind)
    except (bench.NoDevice, registry.LookupFailed) as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    out = traced_run(b, cell, cfg, mix, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices, peak=peak)
    for key, value in readings(out).items():
        print(f"{key} {json.dumps(value)}", flush=True)
    bench.print_run(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
