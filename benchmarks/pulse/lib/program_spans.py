"""The program's own spans in a traced window: the records ``TRACE.stop()``
returns (``repro.core.trace``), and the same spans as ``pulse.*`` host events
of the device trace.

  records  [(name, t0_s, t1_s, parent_index, attrs), ...] on the host clock:
           ``service.round`` / ``.admit`` / ``.init`` / ``.quantum`` /
           ``.apply`` / ``.emit`` and ``engine.execute`` / ``.dispatch`` /
           ``.launch`` / ``.d2h``; ``host_syncs`` counts sit in the attrs
  events   [[name, start_ns, end_ns], ...] of the ``pulse.*`` host events,
           on the device trace's clock

A read quantum is an ``engine.execute`` whose ``path`` is not a write path.
The readers return None where the program kept no records (a program
without the tracer, or a run that did not turn it on).
"""

from __future__ import annotations

import bisect
import heapq

import devtrace

PREFIX = "pulse."
WRITE_PATHS = ("commit_oracle", "distributed_commit")


def load_events(xplane_path: str) -> list[list]:
    """The ``pulse.*`` host events of a profiler trace, sorted by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(xplane_path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = float(e.start_ns)
                    out.append([e.name, s, s + float(e.duration_ns)])
    out.sort(key=lambda x: x[1])
    return out


def _named(records, name: str) -> list[int]:
    return [i for i, r in enumerate(records) if r[0] == name]


def rounds(records) -> int:
    return len(_named(records, "service.round"))


def read_quanta(records) -> list[int]:
    return [i for i in _named(records, "engine.execute")
            if records[i][4].get("path") not in WRITE_PATHS]


def total_s(records, name: str) -> float:
    return sum(records[i][2] - records[i][1] for i in _named(records, name))


def self_s(records, name: str, child: str) -> float:
    """Total time of the ``name`` spans less that of their ``child`` children."""
    inner = sum(t1 - t0 for n, t0, t1, p, _ in records
                if n == child and p is not None and records[p][0] == name)
    return total_s(records, name) - inner


def per_round_ms(records, seconds: float) -> float | None:
    n = rounds(records)
    return 1e3 * seconds / n if n else None


def per_read_quantum(records, value: float) -> float | None:
    n = len(read_quanta(records))
    return value / n if n else None


def engine_coverage(records) -> float | None:
    """Share of the read quanta's ``engine.execute`` time that their
    ``engine.*`` children cover."""
    quanta = set(read_quanta(records))
    if not quanta:
        return None
    whole = sum(records[i][2] - records[i][1] for i in quanta)
    inner = sum(t1 - t0 for n, t0, t1, p, _ in records
                if p in quanta and n.startswith("engine."))
    return inner / whole if whole > 0 else None


# ----------------------------- device trace ------------------------------


def _covers(events, prefix: str):
    """A test of whether an instant lies inside a span named ``prefix*``."""
    merged = devtrace.merge((s, e) for n, s, e in events if n.startswith(prefix))
    starts = [s for s, _ in merged]

    def inside(t: float) -> bool:
        k = bisect.bisect_right(starts, t)
        return k > 0 and t <= merged[k - 1][1]

    return inside


def eager_programs(trace: dict) -> float | None:
    """Executables ("XLA Modules") whose midpoint lies inside a
    ``pulse.service.*`` span and outside every ``pulse.engine.*`` span, per
    ``pulse.service.round``, averaged over the devices."""
    events = (trace or {}).get("program")
    if not events or not trace["devices"]:
        return None
    n_rounds = sum(n == PREFIX + "service.round" for n, _, _ in events)
    if not n_rounds:
        return None
    in_service = _covers(events, PREFIX + "service.")
    in_engine = _covers(events, PREFIX + "engine.")
    count = 0
    for dev in trace["devices"].values():
        for _, s, e in dev["modules"]:
            mid = 0.5 * (s + e)
            count += in_service(mid) and not in_engine(mid)
    return count / len(trace["devices"]) / n_rounds


def _innermost(events, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """Cut [lo, hi] at every span boundary; each piece is named by the
    innermost span open over it (the one that began last; of two that began
    together, the shorter, and of two alike, the later in the list), or
    ``none``."""
    cuts = sorted({lo, hi} | {x for _, s, e in events for x in (s, e) if lo < x < hi})
    order = sorted(range(len(events)), key=lambda i: events[i][1])
    heap: list[tuple[float, float, int]] = []
    k, out = 0, []
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and events[order[k]][1] <= a:
            i = order[k]
            heapq.heappush(heap, (-events[i][1], events[i][2], -i))
            k += 1
        # drop ended spans from the top; one that ended under a span still
        # open is dropped once it surfaces
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        name = events[-heap[0][2]][0][len(PREFIX):] if heap else "none"
        out.append((a, b, name))
    return out


def idle_by_program_span(trace: dict) -> dict[str, float] | None:
    """Device idle seconds in the window, split by the innermost ``pulse.*``
    span open over each idle instant (``none``: under no program span),
    averaged over the devices."""
    events = (trace or {}).get("program")
    if events is None or not trace["devices"]:
        return None
    lo, hi = devtrace.window(trace)
    pieces = _innermost(events, lo, hi)
    out: dict[str, float] = {}
    n_dev = len(trace["devices"])
    for dev in trace["devices"].values():
        busy = devtrace.busy_intervals(dev, lo, hi)
        starts = [s for s, _ in busy]
        before = [0.0]  # busy ns before each busy interval's start
        for s, e in busy:
            before.append(before[-1] + e - s)

        def busy_until(t: float) -> float:
            k = bisect.bisect_right(starts, t)
            if k == 0:
                return 0.0
            s, e = busy[k - 1]
            return before[k - 1] + min(t, e) - s

        for a, b, name in pieces:
            idle = (b - a) - (busy_until(b) - busy_until(a))
            out[name] = out.get(name, 0.0) + idle / 1e9 / n_dev
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def consistency(records, service_self_ms: float | None) -> dict:
    """The two checks the spans are held to: the program's round self time
    (``service.round`` less its ``service.quantum`` children) against the
    benchmark's ``service_self_ms_per_round``, and the share of
    ``engine.execute`` that the ``engine.*`` children cover."""
    own = per_round_ms(records, self_s(records, "service.round", "service.quantum"))
    return {
        "round_self_ms": own,
        "service_self_ms_per_round": service_self_ms,
        "round_self_ratio": (own / service_self_ms
                             if own is not None and service_self_ms else None),
        "engine_children_share": engine_coverage(records),
    }
