"""From the profiler's trace to numbers: the reduction every PR computes the
same way.

``load_events`` reads the ``.xplane.pb`` the JAX profiler wrote into a small
dict of plain lists (nanoseconds, one clock):

  {"host":    [[name, start, end], ...]     # the benchmark's bench.* spans
   "devices": {"/device:TPU:0": {"ops": [[name, start, end], ...],
                                 "modules": [[name, start, end], ...]}, ...}}

and everything below works on that dict, so the recorded sample in
``tests/data`` checks the reduction without a chip.

  busy      union of the intervals in which an operation ran on a device
            (its "XLA Ops" line; "XLA Modules" where it has no op line),
            clipped to the ``bench.window`` span
  idle      1 - busy / window, averaged over the devices
  in_spans  device time of the executables ("XLA Modules") whose midpoint
            lies inside a host span of a given prefix
  collective  device time of collective operations (all-to-all, all-gather,
            all-reduce, reduce-scatter, collective-permute, send/recv)
"""

from __future__ import annotations

import re

HOST_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|^send|^recv|all_to_all|ppermute|psum",
    re.IGNORECASE,
)


def load_events(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    host, devices = [], {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OP_LINE: "ops", MODULE_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    s = float(e.start_ns)
                    dev[key].append([short_name(e.name), s, s + float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        s = float(e.start_ns)
                        host.append([e.name, s, s + float(e.duration_ns)])
    host.sort(key=lambda x: x[1])
    return {"host": host, "devices": devices}


def only_devices(ev: dict, ids) -> dict:
    """The trace of the devices with these ids only."""
    keep = {f"/device:TPU:{i}" for i in ids}
    return {"host": ev["host"],
            "devices": {n: d for n, d in ev["devices"].items() if n in keep}}


def short_name(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.12 = (...)`` ->
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def window(ev: dict) -> tuple[float, float]:
    spans = [h for h in ev["host"] if h[0] == HOST_PREFIX + "window"]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return spans[0][1], spans[0][2]


def merge(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_intervals(dev: dict, lo: float, hi: float) -> list[list[float]]:
    events = dev["ops"] or dev["modules"]
    return merge(_clip(((s, e) for _, s, e in events), lo, hi))


def busy_ns(ev: dict) -> dict[str, float]:
    """Busy nanoseconds of each device inside the window."""
    lo, hi = window(ev)
    return {
        name: sum(e - s for s, e in busy_intervals(dev, lo, hi))
        for name, dev in sorted(ev["devices"].items())
    }


def busy_s_mean(ev: dict) -> float | None:
    b = busy_ns(ev)
    return sum(b.values()) / len(b) / 1e9 if b else None


def idle_pct(ev: dict) -> float | None:
    busy = busy_s_mean(ev)
    if busy is None:
        return None
    lo, hi = window(ev)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))


def in_spans_ns(ev: dict, prefix: str) -> dict[str, float]:
    """Per device: the time of executables whose midpoint lies inside a host
    span whose name starts with ``prefix``."""
    spans = merge((s, e) for n, s, e in ev["host"] if n.startswith(prefix))
    out = {}
    for name, dev in sorted(ev["devices"].items()):
        total = 0.0
        for _, s, e in dev["modules"]:
            mid = 0.5 * (s + e)
            if _inside(spans, mid):
                total += e - s
        out[name] = total
    return out


def _inside(merged: list[list[float]], t: float) -> bool:
    lo, hi = 0, len(merged)
    while lo < hi:  # last interval starting at or before t
        m = (lo + hi) // 2
        if merged[m][0] <= t:
            lo = m + 1
        else:
            hi = m
    return lo > 0 and t <= merged[lo - 1][1]


def collective_ns(ev: dict) -> dict[str, float]:
    """Per device: the time of collective operations inside the window."""
    lo, hi = window(ev)
    out = {}
    for name, dev in sorted(ev["devices"].items()):
        out[name] = sum(
            e - s for n, s, e in _named_clip(dev["ops"], lo, hi) if COLLECTIVE.search(n)
        )
    return out


def _named_clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def _op_kind(name: str) -> str:
    return re.sub(r"[.\d]+$", "", name) or name


def self_times(events) -> list[tuple[str, float]]:
    """Each event's time less that of the events nested inside it (a while
    loop's time less its body's operations)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] - e[1] for e in events]
    stack: list[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:  # nested, not merely overlapping
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][0], own[i]) for i in range(len(events))]


def host_span_at(host: list, t: float) -> str:
    """The innermost bench.* span open at ``t`` (the one that began last)."""
    best, best_start = "no_bench_span", float("-inf")
    for n, s, e in host:
        if s > t:
            break
        if e >= t and s >= best_start and n != HOST_PREFIX + "window":
            best, best_start = n, s
    return best


def breakdown(ev: dict, top: int = 10) -> dict:
    """The device operations that took most time (their own seconds, nested
    operations taken out, averaged over the devices, by operation kind) and
    the longest idle gaps of the devices, each named by the host span open
    over its middle."""
    lo, hi = window(ev)
    n_dev = max(1, len(ev["devices"]))
    per_op: dict[str, float] = {}
    gaps = []
    for name, dev in sorted(ev["devices"].items()):
        for n, own in self_times(_named_clip(dev["ops"] or dev["modules"], lo, hi)):
            k = _op_kind(n)
            per_op[k] = per_op.get(k, 0.0) + own / 1e9 / n_dev
        busy = busy_intervals(dev, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = [
        [host_span_at(ev["host"], 0.5 * (a + b)), d / 1e9] for d, a, b in gaps[:top]
    ]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
