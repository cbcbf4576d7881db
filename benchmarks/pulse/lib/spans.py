"""Spans taken from the benchmark's own files, around the calls into each
layer of the program, and the compile counters.

A span is a host-clock interval (``time.perf_counter``) kept in memory, and
the same interval as a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``
so that it sits on the device trace's clock.  Spans are recorded only while
``Spans.on`` (the traced run's window); otherwise they cost one attribute
test.

  bench.round              one ``PulseService.step``
  bench.quantum.<iterator> one ``engine.execute`` (a quantum), wrapped on the
                           service's engine instance; its record carries the
                           ``ExecResult.path`` and the lanes' iterations
  bench.submit             the loop handing due requests to the service
  bench.retire             the loop collecting retired requests
  bench.wait               the open loop sleeping until the next request is due
  bench.window             the measured window
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

import jax


class Spans:
    def __init__(self):
        self.on = False
        self.records: list[tuple[str, float, float, dict]] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        if not self.on:
            yield
            return
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.records.append((name, t0, time.perf_counter(), attrs))

    def named(self, prefix: str) -> list[tuple[str, float, float, dict]]:
        return [r for r in self.records if r[0].startswith(prefix)]


def instrument_engine(engine, spans: Spans, null_ptr: int) -> None:
    """Wrap the engine instance's ``execute`` so each quantum is a span."""
    inner = engine.execute

    def execute(it, ptr0, scratch0, **kw):
        if not spans.on:
            return inner(it, ptr0, scratch0, **kw)
        occupied = np.asarray(ptr0) != null_ptr
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.quantum." + it.name):
            res = inner(it, ptr0, scratch0, **kw)
        t1 = time.perf_counter()
        spans.records.append((
            "quantum." + it.name, t0, t1,
            {"path": res.path, "writes": bool(it.mutates),
             "lane_iters": int(np.asarray(res.iters)[occupied].sum())},
        ))
        return res

    engine.execute = execute


class CompileCounter:
    """Counts JAX traces and executables obtained (compiled or read from the
    persistent cache), plus the program's own retrace counters."""

    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.counts = {"jax_traces": 0, "jax_compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self._TRACE:
            self.counts["jax_traces"] += 1
        elif event == self._COMPILE:
            self.counts["jax_compiles"] += 1

    def snapshot(self) -> dict:
        from repro.core import routing
        from repro.kernels.pulse_chase import ops as chase_ops

        return {
            **self.counts,
            "routing_traces": routing.CACHE_STATS.traces,
            "kernel_traces": chase_ops.CACHE_STATS.traces,
        }

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}

    @staticmethod
    def compiles(delta: dict) -> int:
        """Executables obtained plus the program's retraces; a JAX trace that
        compiles nothing (an eager operation's) is not a compile."""
        return sum(v for k, v in delta.items() if k != "jax_traces")


class GcPauses:
    """Times of the interpreter's full (generation 2) collections, which stop
    the serving thread."""

    def __init__(self):
        self.pauses_ms: list[float] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses_ms.append((time.perf_counter() - self._t0) * 1e3)

    def summary(self, since: int) -> dict:
        p = self.pauses_ms[since:]
        return {"count": len(p), "max_ms": max(p, default=0.0), "total_ms": sum(p)}
