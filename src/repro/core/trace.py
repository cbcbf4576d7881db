"""Spans and counts inside the program: where a serving round's host time goes.

One module-level tracer, ``TRACE``, is the program's only span system.  It is
off unless ``TRACE.start()`` turned it on:

  * off: ``TRACE.span(...)`` returns one shared no-op context object and
    ``TRACE.count(...)`` returns at once -- one attribute test each, no
    allocation, no clock read, no ``jax.profiler`` call;
  * on: each span keeps ``(name, t0, t1, parent, attrs)`` in memory on the
    ``time.perf_counter`` clock, and opens a
    ``jax.profiler.TraceAnnotation("pulse." + name)``, so the same interval
    sits on a device trace's clock.  The parent is the innermost span open
    on the same thread, so spans opened on a ``DeviceRunner`` thread nest
    under that thread's own spans.  A span that names no ``round`` takes its
    parent's, so every span of one service round carries the same ``round``.

``TRACE.count(key, n)`` adds ``n`` to the attribute ``key`` of the innermost
open span of the calling thread; counts live at the same boundaries as spans.
``TRACE.stop()`` turns the tracer off and returns the records of the spans
that ended, in the order they were opened, each parent as an index into that
list (``None`` for a root, or for a span whose parent had not ended).

Spans the serving path opens:

  service.round    ``PulseService.step``, attrs ``round``
  service.admit    ``_admit``: intake, preemption, barriers, admission, slots
  service.init     inside ``service.admit``: one group's compiled ``init``
  service.quantum  one group's quantum (``_make_work``'s ``run``), attrs ``round``
  service.apply    ``_apply_result``: slot scatter and retirement
  service.emit     ``_drain_emit``: retirement accounting
  engine.execute   ``PulseEngine.execute``, attrs ``lanes``, ``path``
  engine.dispatch  the offload decision and the access-check elision
  engine.launch    the local jitted step's enqueue (compiles show here)
  engine.d2h       the blocking reads of the local step's results

and two counts: ``host_syncs``, blocking device-to-host reads on the served
path (the result reads, the ``perms`` scan of
``routing.can_elide_access_check``, and an ``init``'s one read where JAX
exposes no host CPU device); ``host_inits``, fresh batches whose ``init``
ran on the host CPU device.
"""

from __future__ import annotations

import threading
import time

import jax

# attributes a span takes from its parent when it names none itself
INHERITED = ("round",)


class _Off:
    """The context a span is while the tracer is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("tracer", "rec", "ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        # [name, t0, t1, parent record, attrs]
        self.rec = [name, 0.0, None, None, attrs]

    def __enter__(self):
        tr, rec = self.tracer, self.rec
        stack = tr._stack()
        if stack:
            parent = stack[-1]
            rec[3] = parent
            for k in INHERITED:
                if k in parent[4] and k not in rec[4]:
                    rec[4][k] = parent[4][k]
        stack.append(rec)
        tr._records.append(rec)
        self.ann = jax.profiler.TraceAnnotation("pulse." + rec[0])
        self.ann.__enter__()
        rec[1] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.rec[2] = time.perf_counter()
        self.ann.__exit__(*exc)
        self.tracer._stack().pop()
        return False

    def set(self, **attrs) -> None:
        """Attributes known only once the span's work ran."""
        self.rec[4].update(attrs)


class Tracer:
    def __init__(self):
        self.on = False
        self._records: list = []
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs):
        if not self.on:
            return _OFF
        return _Span(self, name, attrs)

    def count(self, key: str, n: int = 1) -> None:
        if not self.on:
            return
        stack = self._stack()
        if stack:
            attrs = stack[-1][4]
            attrs[key] = attrs.get(key, 0) + n

    def start(self) -> None:
        self._records = []
        self.on = True

    def stop(self) -> list[tuple[str, float, float, int | None, dict]]:
        self.on = False
        done = [r for r in self._records if r[2] is not None]
        self._records = []
        index = {id(r): i for i, r in enumerate(done)}
        return [
            (name, t0, t1, None if parent is None else index.get(id(parent)), attrs)
            for name, t0, t1, parent, attrs in done
        ]


TRACE = Tracer()
