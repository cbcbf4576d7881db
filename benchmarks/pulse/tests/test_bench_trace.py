"""The reduction from a device trace to the per-layer numbers: a hand-built
trace with hand-worked values, and a trace recorded on a TPU v5 lite
(``data/trace_webservice_open.json``: 45 ms of a ``webservice.ycsb-c.open``
window, times rebased to its start)."""

import json
from pathlib import Path

import numpy as np
import pytest

import devtrace

HAND = {
    "host": [
        ["bench.window", 0, 1000],
        ["bench.round", 100, 400],
        ["bench.quantum.hash_find", 150, 350],
        ["bench.wait", 500, 900],
        ["bench.round", 900, 1000],
    ],
    "devices": {
        "/device:TPU:0": {
            "ops": [["while.1", 200, 300], ["fusion.2", 210, 240], ["fusion.3", 250, 280],
                    ["all-to-all.4", 600, 650], ["copy.5", -50, 20], ["add.9", 955, 985]],
            "modules": [["jit_step(1)", 190, 310], ["jit_x(2)", 600, 660], ["jit_y(3)", 950, 990]],
        },
        "/device:TPU:1": {
            "ops": [["collective-permute-start.1", 100, 200], ["fusion.7", 150, 250],
                    ["add.8", 960, 980]],
            "modules": [["jit_step(1)", 100, 260]],
        },
    },
}


def test_busy_union_and_idle_share_by_hand():
    # TPU:0: copy clipped to [0,20] 20 + while (covers its fusions) 100
    #        + all-to-all 50 + add 30 = 200
    # TPU:1: [100,250] union of the permute and the fusion 150 + add 20 = 170
    assert devtrace.busy_ns(HAND) == {"/device:TPU:0": 200, "/device:TPU:1": 170}
    assert devtrace.busy_s_mean(HAND) == pytest.approx(185e-9)
    assert devtrace.idle_pct(HAND) == pytest.approx(81.5)


def test_collective_time_by_hand():
    assert devtrace.collective_ns(HAND) == {"/device:TPU:0": 50, "/device:TPU:1": 100}


def test_device_time_inside_host_spans_by_hand():
    # executables whose midpoint lies in the quantum span [150, 350]
    assert devtrace.in_spans_ns(HAND, "bench.quantum.") == {
        "/device:TPU:0": 120, "/device:TPU:1": 160,
    }


def test_breakdown_by_hand():
    bd = devtrace.breakdown(HAND)
    # own time, nested operations taken out, averaged over the two devices:
    # fusion (60 + 100) / 2, permute 100 / 2, all-to-all 50 / 2,
    # add (30 + 20) / 2, while (100 - 60) / 2, copy 20 / 2
    assert [k for k, _ in bd["device_ops"]] == [
        "fusion", "collective-permute-start", "all-to-all", "add", "while", "copy",
    ]
    np.testing.assert_allclose(
        [v for _, v in bd["device_ops"]], [80e-9, 50e-9, 25e-9, 25e-9, 20e-9, 10e-9]
    )
    # gaps: TPU:1 [250,960] under the wait; TPU:0 [650,955] under the wait,
    # [300,600] under no span, [20,200] in a round; TPU:1 [0,100] under no
    # span, [980,1000] and TPU:0 [985,1000] in the last round
    assert bd["idle_gaps"] == [
        ["bench.wait", 710e-9], ["bench.wait", 305e-9], ["no_bench_span", 300e-9],
        ["bench.round", 180e-9], ["no_bench_span", 100e-9], ["bench.round", 20e-9],
        ["bench.round", 15e-9],
    ]


RECORDED = json.loads(
    (Path(__file__).parent / "data" / "trace_webservice_open.json").read_text()
)


def test_recorded_trace_busy_matches_a_nanosecond_raster():
    lo, hi = devtrace.window(RECORDED)
    grid = np.zeros(int(hi - lo), bool)
    for _, s, e in RECORDED["devices"]["/device:TPU:0"]["ops"]:
        grid[int(max(s, lo) - lo): int(min(e, hi) - lo)] = True
    assert devtrace.busy_ns(RECORDED)["/device:TPU:0"] == grid.sum()
    assert devtrace.idle_pct(RECORDED) == pytest.approx(100 * (1 - grid.sum() / (hi - lo)))


def test_recorded_trace_reads_as_measured():
    """The numbers this sample gave when the reduction was written."""
    assert devtrace.busy_s_mean(RECORDED) == pytest.approx(0.000554958)
    assert devtrace.idle_pct(RECORDED) == pytest.approx(98.76676)
    assert devtrace.in_spans_ns(RECORDED, "bench.quantum.") == {"/device:TPU:0": 533723.0}
    assert devtrace.collective_ns(RECORDED) == {"/device:TPU:0": 0}
    bd = devtrace.breakdown(RECORDED)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert bd["device_ops"][0][0] == "fusion"
    assert {n for n, _ in bd["idle_gaps"]} <= {
        "bench.round", "bench.quantum.hash_find", "bench.wait", "bench.submit",
        "bench.retire", "no_bench_span",
    }


def test_only_the_chips_that_hold_the_arena():
    one = devtrace.only_devices(HAND, [1])
    assert list(one["devices"]) == ["/device:TPU:1"]
    assert devtrace.busy_ns(one) == {"/device:TPU:1": 170}
    assert one["host"] is HAND["host"]
