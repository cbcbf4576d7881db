"""The load loops: an open loop at a fixed Poisson rate and a closed loop of
a fixed number of clients, both on one thread over ``PulseService``'s public
``submit``/``step``.

Every request's latency runs from the time it was *due* -- on the arrival
schedule in the open loop, when its client's previous reply came in the
closed loop -- to its ``finish_s``, both on ``time.perf_counter``.  So a
long round that delays the next submissions shows in the latencies of the
requests it delayed.  How late the loop itself handed each request over
(``submit_s - due_s``) is kept apart as the generator's lag.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from spans import Spans
from traffic import OPS, Batch, arrival_offsets

DRAIN_LIMIT_S = 60.0  # how long past the window's close a due answer is awaited


@dataclasses.dataclass
class Window:
    start: float
    end: float
    first: int  # log index of the window's first request
    stop: int  # one past its last (requests after it are read-back)
    loop: str


class Client:
    """Submits requests and keeps every submitted request, in submission
    order, with its due and submit times."""

    def __init__(self, service, op_spec: dict, spans: Spans, tenant: str = "ycsb"):
        from repro.core.iterator import STATUS_SHED
        from repro.serving.admission import TraversalRequest

        self._req = TraversalRequest
        self._shed = STATUS_SHED
        self.service = service
        self.op_spec = op_spec
        self.spans = spans
        self.tenant = tenant
        self.log: list = []
        self.op: list[int] = []
        self.due: list[float] = []
        self.submit_s: list[float] = []
        self.outstanding: list[int] = []

    def submit(self, batch: Batch, i: int, due_s: float) -> None:
        op = int(batch.op[i])
        r = self._req(
            req_id=int(batch.req_id[i]), structure=self.op_spec[OPS[op]],
            query=int(batch.key[i]), value=int(batch.value[i]), tenant=self.tenant,
        )
        self.service.submit(r)
        self.outstanding.append(len(self.log))
        self.log.append(r)
        self.op.append(op)
        self.due.append(due_s)
        self.submit_s.append(time.perf_counter())

    def step(self) -> int:
        """One service round; returns how many requests retired in it."""
        with self.spans.span("round"):
            self.service.step()
        with self.spans.span("retire"):
            keep = []
            for j in self.outstanding:
                r = self.log[j]
                if r.finish_s < 0 and r.status != self._shed:
                    keep.append(j)
            done = len(self.outstanding) - len(keep)
            self.outstanding = keep
        return done

    def drain(self, limit_s: float) -> None:
        while self.outstanding and time.perf_counter() < limit_s:
            self.step()

    def serve_all(self, batch: Batch, limit_s: float) -> None:
        """Submit the whole batch at once and wait for it (warm-up, read-back)."""
        now = time.perf_counter()
        for i in range(len(batch.op)):
            self.submit(batch, i, now)
        self.drain(limit_s)


def open_loop(client: Client, batch: Batch, rate_rps: float, seconds: float,
              rng, on_close=None) -> Window:
    """Submit ``batch`` on a Poisson schedule at ``rate_rps`` over ``seconds``
    (all of it is due inside the window), then await every answer up to
    ``DRAIN_LIMIT_S`` past the close."""
    due = arrival_offsets(rate_rps, seconds, rng)
    n = len(due)
    assert len(batch.op) >= n
    spans = client.spans
    first = len(client.log)
    t0 = time.perf_counter()
    end = t0 + seconds
    due_abs = t0 + due
    i = 0
    closed = False
    while True:
        now = time.perf_counter()
        if not closed and now >= end:
            closed = True
            if on_close is not None:
                on_close()
        if i < n and due_abs[i] <= now:
            with spans.span("submit"):
                while i < n and due_abs[i] <= now:
                    client.submit(batch, i, float(due_abs[i]))
                    i += 1
        if client.outstanding:
            if now > end + DRAIN_LIMIT_S:
                break
            client.step()
        elif i >= n:
            break
        else:
            wait = due_abs[i] - time.perf_counter()
            if wait > 0:
                with spans.span("wait"):
                    time.sleep(wait)
    if not closed and on_close is not None:
        on_close()
    return Window(t0, end, first, len(client.log), "open")


def closed_loop(client: Client, next_batch, clients: int, seconds: float,
                on_close=None) -> Window:
    """``clients`` clients, each sending its next request when its reply
    arrives, for ``seconds``; then await the requests still out."""
    spans = client.spans
    pending: list[tuple[Batch, int]] = []

    def take() -> tuple[Batch, int]:
        if not pending:
            b = next_batch()
            pending.extend((b, i) for i in range(len(b.op) - 1, -1, -1))
        return pending.pop()

    first = len(client.log)
    t0 = time.perf_counter()
    end = t0 + seconds
    with spans.span("submit"):
        for _ in range(clients):
            client.submit(*take(), t0)
    while True:
        done = client.step()
        now = time.perf_counter()
        if now >= end:
            break
        if done:
            with spans.span("submit"):
                for _ in range(done):
                    client.submit(*take(), now)
    if on_close is not None:
        on_close()
    client.drain(end + DRAIN_LIMIT_S)
    return Window(t0, end, first, len(client.log), "closed")


def latencies_ms(client: Client, win: Window, give_up_s: float) -> np.ndarray:
    """Due-to-finish latency of every request of the window; a request that
    never finished counts as taking until ``give_up_s``."""
    out = np.empty(win.stop - win.first)
    for k, j in enumerate(range(win.first, win.stop)):
        f = client.log[j].finish_s
        out[k] = ((f if f >= 0 else give_up_s) - client.due[j]) * 1e3
    return out


def nearest_rank(x: np.ndarray, q: float) -> float:
    """The ``q``-quantile by nearest rank (an observed latency, no
    interpolation)."""
    s = np.sort(np.asarray(x, np.float64))
    return float(s[max(0, int(np.ceil(q * len(s))) - 1)])
