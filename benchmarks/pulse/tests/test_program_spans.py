"""The readers of the program's own spans (``lib/program_spans.py`` and the
``metrics/`` that use it): hand-built records and a hand-built trace with
hand-worked values, the split of a profiler trace's host events, and a whole
run of a cell on the CPU at a tiny size with the tracer on."""

import types

import jax
import jax.numpy as jnp
import pytest

import devtrace
import program_spans
import registry

READERS = (
    "admit_self_ms_per_round.tput", "init_ms_per_round.tput", "retire_ms_per_round.tput",
    "dispatch_ms_per_quantum.tput", "device_wait_ms_per_quantum.tput",
    "host_syncs_per_quantum.tput", "eager_programs_per_round.tput",
)

# three rounds, in seconds: one that admits, one that does not, one write quantum
RECORDS = [
    ("service.round", 0.000, 0.010, None, {"round": 0}),
    ("service.admit", 0.000, 0.004, 0, {"round": 0}),
    ("service.init", 0.001, 0.003, 1, {"round": 0, "host_syncs": 2}),
    ("service.quantum", 0.004, 0.008, 0, {"round": 0}),
    ("engine.execute", 0.004, 0.008, 3, {"round": 0, "lanes": 32, "path": "xla"}),
    ("engine.dispatch", 0.004, 0.005, 4, {"round": 0}),
    ("engine.launch", 0.005, 0.0055, 4, {"round": 0}),
    ("engine.d2h", 0.0055, 0.0075, 4, {"round": 0, "host_syncs": 4}),
    ("service.apply", 0.008, 0.009, 0, {"round": 0}),
    ("service.emit", 0.009, 0.010, 0, {"round": 0}),
    ("service.round", 0.010, 0.016, None, {"round": 1}),
    ("service.admit", 0.010, 0.011, 10, {"round": 1}),
    ("service.quantum", 0.011, 0.015, 10, {"round": 1}),
    ("engine.execute", 0.011, 0.015, 12, {"round": 1, "lanes": 32, "path": "xla"}),
    ("engine.dispatch", 0.011, 0.013, 13, {"round": 1}),
    ("engine.launch", 0.013, 0.0135, 13, {"round": 1}),
    ("engine.d2h", 0.0135, 0.0145, 13, {"round": 1, "host_syncs": 4}),
    ("service.apply", 0.015, 0.0155, 10, {"round": 1}),
    ("service.emit", 0.0155, 0.016, 10, {"round": 1}),
    ("service.round", 0.016, 0.020, None, {"round": 2}),
    ("service.quantum", 0.016, 0.020, 19, {"round": 2}),
    ("engine.execute", 0.016, 0.020, 20, {"round": 2, "lanes": 32, "path": "commit_oracle"}),
]

# by hand: admit less init 2 + 1 ms over 3 rounds; init 2 ms over 3;
# apply + emit 1 + 1 + 0.5 + 0.5 ms over 3; over the 2 read quanta:
# dispatch 1 + 2 ms, d2h 2 + 1 ms, host syncs 2 + 4 + 4
BY_HAND = {
    "admit_self_ms_per_round.tput": 1.0,
    "init_ms_per_round.tput": 2 / 3,
    "retire_ms_per_round.tput": 1.0,
    "dispatch_ms_per_quantum.tput": 1.5,
    "device_wait_ms_per_quantum.tput": 1.5,
    "host_syncs_per_quantum.tput": 5.0,
}

# nanoseconds on the trace's clock
TRACE = {
    "host": [["bench.window", 0, 1000]],
    "devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 130, 140], ["fusion.2", 150, 160], ["while.3", 250, 350],
                ["copy.5", 540, 560], ["add.4", 700, 720]],
        "modules": [["jit_add(1)", 130, 140], ["jit_take(2)", 150, 160],
                    ["jit_chase_local(3)", 250, 350], ["jit_y(5)", 540, 560],
                    ["jit_x(4)", 700, 720]],
    }},
    "program": [
        ["pulse.service.round", 100, 600],
        ["pulse.service.admit", 100, 200],
        ["pulse.service.init", 120, 180],
        ["pulse.service.quantum", 200, 500],
        ["pulse.engine.execute", 210, 490],
        ["pulse.engine.d2h", 300, 480],
        ["pulse.service.emit", 500, 600],
    ],
}


def _view(records=None, trace=None):
    return types.SimpleNamespace(program=records, trace=trace)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_each_reader_by_hand(name):
    v = registry.metric_module(name).read(_view(RECORDS))
    assert v == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", READERS)
def test_a_run_without_program_spans_reads_nothing(name):
    """The benchmark's view of a program without the tracer has no
    ``program`` field, and its trace no ``pulse.*`` events."""
    bare = types.SimpleNamespace(trace={k: v for k, v in TRACE.items() if k != "program"})
    assert registry.metric_module(name).read(bare) is None
    assert registry.metric_module(name).read(_view([], None)) is None


def test_consistency_by_hand():
    c = program_spans.consistency(RECORDS, 2.5)
    # round less its quantum: 6 + 2 + 0 ms over 3 rounds
    assert c["round_self_ms"] == pytest.approx(8 / 3)
    assert c["round_self_ratio"] == pytest.approx(8 / 3 / 2.5)
    # children of the read quanta: 3.5 of 4 ms, twice
    assert c["engine_children_share"] == pytest.approx(0.875)


def test_idle_by_program_span_by_hand():
    got = program_spans.idle_by_program_span(TRACE)
    want = {"none": 480, "engine.d2h": 130, "service.emit": 80, "engine.execute": 50,
            "service.admit": 40, "service.init": 40, "service.quantum": 20}
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx((1000 - 160) / 1e9)  # the window's idle


def test_eager_programs_by_hand():
    # jit_add, jit_take (inside init) and jit_y (inside emit); the chase runs
    # inside the engine, jit_x under no program span
    assert registry.metric_module("eager_programs_per_round.tput").read(
        _view(None, TRACE)) == pytest.approx(3.0)


def test_a_profiler_trace_splits_into_bench_and_program_events(tmp_path):
    """``devtrace.load_events`` keeps only ``bench.*`` host events, as it
    always has; the program's ``pulse.*`` events are read beside them."""
    from repro.core.trace import TRACE as TRACER

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        TRACER.start()
        with TRACER.span("service.round", round=0):
            with TRACER.span("engine.execute"):
                jnp.arange(8).sum().block_until_ready()
        records = TRACER.stop()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    assert [h[0] for h in devtrace.load_events(str(path))["host"]] == ["bench.window"]
    events = program_spans.load_events(str(path))
    assert [e[0] for e in events] == ["pulse.service.round", "pulse.engine.execute"]
    assert events[0][1] <= events[1][1] and events[1][2] <= events[0][2]
    assert [r[0] for r in records] == ["service.round", "engine.execute"]


def test_a_tiny_cell_on_the_cpu_reads_every_program_metric():
    import bench
    import program_trace
    from scenarios import SECONDS, TINY

    b = registry.load_benchmark()
    cell = registry.workload(b, "webservice.ycsb-c.closed")
    cfg = {**registry.config(b, cell["config"]), **TINY["hash_table"]}
    mix = registry.traffic(cell["traffic"])
    bench.setup_jax_cache()
    out = program_trace.traced_run(
        b, cell, cfg, mix, seed=2**31 + 29, seconds=SECONDS, trace=False,
        devices=jax.devices()[:1], peak={},
    )
    assert out["result"]["correct"] is True
    lines = program_trace.readings(out)
    got = lines["program_metrics"]
    assert set(got) == set(READERS) - {"eager_programs_per_round.tput"}  # no device trace
    assert all(v > 0 for v in got.values())
    # four result reads a quantum, two more on each round that admits
    assert 4 <= got["host_syncs_per_quantum.tput"] <= 6
    assert 0 < lines["consistency"]["engine_children_share"] <= 1
    assert lines["idle_by_program_span"] is None
    rounds = program_spans.rounds(out["program"])
    assert rounds == len(program_spans.read_quanta(out["program"])) > 0
