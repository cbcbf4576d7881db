"""Benchmark harness: one function per paper table/figure + kernel benches.

Prints ``name,us_per_call,derived`` style CSV (extra keys folded into the
derived column).  Run: ``PYTHONPATH=src python -m benchmarks.run``.
"""

from __future__ import annotations

import sys
import time


def _emit(rows):
    for r in rows:
        name = r.pop("name")
        us = r.pop("us_per_call", "")
        derived = r.pop("derived", "")
        extras = ";".join(f"{k}={v}" for k, v in r.items())
        derived = f"{derived};{extras}".strip(";")
        print(f"{name},{us},{derived}")


def main() -> None:
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import kernel_bench, paper_figures as pf

    t0 = time.time()
    print("name,us_per_call,derived")
    benches = [
        ("table3", pf.table3_workloads),
        ("fig7", pf.fig7_latency_throughput),
        ("fig8", pf.fig8_energy),
        ("fig9", pf.fig9_pulse_acc),
        ("fig10", pf.fig10_breakdown),
        ("table4", pf.table4_pipelines),
        ("fig11", pf.fig11_eta),
        ("fig5", pf.fig5_allocation),
        ("traversal_length", pf.appendix_traversal_length),
        ("bandwidth", pf.appendix_bandwidth),
        ("kernels", kernel_bench.bench_kernels),
    ]
    failed = []
    for name, fn in benches:
        try:
            _emit(fn())
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name},ERROR,{type(e).__name__}: {e}", file=sys.stderr)
            import traceback

            traceback.print_exc()
    print(f"# benchmarks done in {time.time() - t0:.1f}s; failures: {failed or 'none'}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
