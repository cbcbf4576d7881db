"""The program's tracer (``core/trace.py``): the off path keeps nothing and
never touches the profiler; the on path nests the service's and the engine's
spans by round and by thread, and counts every blocking device-to-host read."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isa
from repro.core.engine import PulseEngine
from repro.core.structures import hash_table, isa_programs
from repro.core.trace import TRACE, Tracer
from repro.serving.admission import TraversalRequest
from repro.serving.traversal_service import PulseService, StructureSpec

N_KEYS, N_BUCKETS, SLOTS = 300, 16, 8
SERVICE_SPANS = {"service.admit", "service.quantum", "service.apply", "service.emit"}
ENGINE_SPANS = {"engine.dispatch", "engine.launch", "engine.d2h"}


@pytest.fixture(autouse=True)
def tracer_off():
    TRACE.stop()
    yield
    TRACE.stop()


def _table():
    rng = np.random.default_rng(5)
    keys = rng.choice(10**6, N_KEYS, replace=False).astype(np.int32)
    vals = rng.integers(0, 10**6, N_KEYS).astype(np.int32)
    arena, heads = hash_table.build(keys, vals, N_BUCKETS)
    return arena, heads, keys


def _iterator(kind: str):
    traced = hash_table.find_iterator(N_BUCKETS)
    if kind == "traced":
        return traced
    # the same chain walk as a verified ISA program: its certificate lets the
    # engine scan the arena's perms to elide the access check
    vm = isa.as_pulse_iterator(isa_programs.hash_find_program())
    return dataclasses.replace(vm, init_fn=traced.init_fn)


def _serve(kind="traced", pipeline="sync", trace=True):
    """Warm a small hash service, then serve 24 finds arriving three a round
    (so some rounds admit and some do not); returns (requests, records)."""
    arena, heads, keys = _table()
    svc = PulseService(
        PulseEngine(arena),
        {"h": StructureSpec(_iterator(kind), (jnp.asarray(heads),))},
        slots_per_structure=SLOTS, quantum=4, pipeline=pipeline,
    )
    svc.run([TraversalRequest(1000 + i, "h", int(keys[i])) for i in range(SLOTS)])
    r0 = svc.metrics.rounds
    reqs = [
        TraversalRequest(i, "h", int(keys[(7 * i) % N_KEYS]), arrive_round=r0 + i // 3)
        for i in range(24)
    ]
    if trace:
        TRACE.start()
    svc.run(reqs)
    return reqs, TRACE.stop()


def _children(records):
    kids: dict[int, list[int]] = {}
    for i, (_, _, _, parent, _) in enumerate(records):
        if parent is not None:
            kids.setdefault(parent, []).append(i)
    return kids


def test_off_path_keeps_nothing_and_never_annotates(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("TraceAnnotation constructed while the tracer is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert TRACE.span("a", round=1) is TRACE.span("b")  # one shared no-op
    reqs, records = _serve(trace=False)
    assert records == []
    assert all(r.result is not None for r in reqs)


def test_sync_round_holds_its_service_spans():
    _, records = _serve()
    kids = _children(records)
    rounds = [i for i, r in enumerate(records) if r[0] == "service.round"]
    assert rounds
    assert len({records[i][4]["round"] for i in rounds}) == len(rounds)  # one a round
    admitted = 0
    for i in rounds:
        assert records[i][3] is None
        names = [records[k][0] for k in kids[i]]
        assert set(names) == SERVICE_SPANS, names
        assert names.count("service.quantum") == 1
        for k in kids[i]:
            assert records[k][4]["round"] == records[i][4]["round"]
        admit = next(k for k in kids[i] if records[k][0] == "service.admit")
        inits = [records[k][0] for k in kids.get(admit, [])]
        assert inits in ([], ["service.init"])
        admitted += bool(inits)
    assert 0 < admitted < len(rounds)


def test_read_quantum_holds_its_engine_spans():
    _, records = _serve()
    kids = _children(records)
    quanta = [i for i, r in enumerate(records) if r[0] == "engine.execute"]
    assert len(quanta) == sum(r[0] == "service.quantum" for r in records)
    for i in quanta:
        name, _, _, parent, attrs = records[i]
        assert records[parent][0] == "service.quantum"
        assert attrs["path"] == "xla" and attrs["lanes"] == SLOTS
        assert attrs["round"] == records[parent][4]["round"]
        names = [records[k][0] for k in kids[i]]
        assert set(names) == ENGINE_SPANS, names
    for name, t0, t1, parent, _ in records:
        assert t0 <= t1
        if parent is not None:  # a child never outlasts its parent
            assert records[parent][1] <= t0 and t1 <= records[parent][2], name


@pytest.mark.parametrize("kind,idle,admitting", [("traced", 4, 6), ("certified", 5, 7)])
def test_host_syncs_per_quantum(kind, idle, admitting):
    """Four result reads a quantum, two reads of an eager ``init``, and one
    ``perms`` scan where a certificate lets the engine try to elide the
    access check."""
    _, records = _serve(kind)
    per_round: dict[int, int] = {}
    admits: set[int] = set()
    for name, _, _, _, attrs in records:
        if "round" not in attrs:
            continue
        rnd = attrs["round"]
        per_round[rnd] = per_round.get(rnd, 0) + attrs.get("host_syncs", 0)
        if name == "service.init":
            admits.add(rnd)
    assert admits and set(per_round) - admits
    for rnd, n in per_round.items():
        assert n == (admitting if rnd in admits else idle), (rnd, n)


def test_async_runner_spans_nest_on_their_thread():
    _, records = _serve(pipeline="async")
    quanta = [i for i, r in enumerate(records) if r[0] == "service.quantum"]
    assert quanta
    for i in quanta:  # opened on the runner thread: no parent there
        assert records[i][3] is None
    for name, _, _, parent, attrs in records:
        if name == "engine.execute":
            assert records[parent][0] == "service.quantum"
            assert attrs["round"] == records[parent][4]["round"]


def test_answers_identical_with_tracer_on_and_off():
    off, _ = _serve(trace=False)
    on, records = _serve(trace=True)
    assert records
    for a, b in zip(off, on):
        assert (a.status, a.iters, a.finish_round) == (b.status, b.iters, b.finish_round)
        np.testing.assert_array_equal(a.result, b.result)


def test_tracer_counts_into_the_innermost_span_and_drops_open_ones():
    tr = Tracer()
    tr.count("x")  # off: nothing to count into
    tr.start()
    tr.count("x")  # no span open: dropped

    def other_thread():
        with tr.span("other"):
            tr.count("x")

    with tr.span("outer", round=3):
        tr.count("x", 2)
        with tr.span("inner") as sp:
            tr.count("x")
            sp.set(path="p")
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    with tr.span("open"):
        recs = tr.stop()
    assert [r[0] for r in recs] == ["outer", "inner", "other"]  # "open" never ended
    assert recs[0][3] is None and recs[0][4] == {"round": 3, "x": 2}
    assert recs[1][3] == 0 and recs[1][4] == {"round": 3, "x": 1, "path": "p"}
    assert recs[2][3] is None and recs[2][4] == {"x": 1}  # its own thread's root
    assert not tr.on and tr.stop() == []


def test_tracer_keeps_each_threads_spans_apart_under_contention():
    """More threads than cores, switching often: every span is kept, nests
    under its own thread's parent, and counts only into its own attrs."""
    import os
    import sys

    n_threads, n_spans = (os.cpu_count() or 4) + 4, 100
    tr = Tracer()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        tr.start()

        def work(k):
            for j in range(n_spans):
                with tr.span("outer", round=(k, j)):
                    with tr.span("inner"):
                        tr.count("n")

        threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        recs = tr.stop()
    finally:
        sys.setswitchinterval(interval)
    assert len(recs) == 2 * n_threads * n_spans
    for name, _, _, parent, attrs in recs:
        if name == "outer":
            assert parent is None and "n" not in attrs
        else:
            assert recs[parent][0] == "outer" and recs[parent][4]["round"] == attrs["round"]
            assert attrs["n"] == 1
