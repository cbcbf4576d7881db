"""The harness on the CPU: inputs from the seed, the loops' timing, every
name of BENCHMARK.json found by its file, and refusal without a chip."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import drive
import registry
import traffic
from reference import KEY_NOT_FOUND, KeyValueReference
from spans import Spans

BENCH_DIR = registry.BENCH_DIR
CHECKOUT = registry.CHECKOUT
CFG = {"miss_rate": 0.05, "zipf_constant": 0.99}
MIX_B = {"ops": {"find": 0.95, "update": 0.05}}


def _inputs(seed, n=2000):
    keys, values = traffic.make_data(5000, np.random.default_rng([seed, 0]))
    gen = traffic.Generator(MIX_B, CFG, keys, np.random.default_rng([seed, 1]), seed)
    rng = np.random.default_rng([seed, 3])
    return keys, values, gen.batch(n, rng), traffic.arrival_offsets(500.0, 4.0, rng)


def test_same_seed_gives_the_same_inputs():
    seed = 2**32 + 12345  # seeds may exceed 32 signed bits
    a, b = _inputs(seed), _inputs(seed)
    for x, y in zip(a[:2], b[:2]):
        np.testing.assert_array_equal(x, y)
    for f in ("op", "key", "value", "req_id"):
        np.testing.assert_array_equal(getattr(a[2], f), getattr(b[2], f))
    np.testing.assert_array_equal(a[3], b[3])
    c = _inputs(seed + 1)
    assert not np.array_equal(a[2].key, c[2].key)


def test_every_seed_gets_the_same_amount_of_work():
    batches = [_inputs(s)[2] for s in (1, 2, 3)]
    dues = [_inputs(s)[3] for s in (1, 2, 3)]
    for b in batches:
        assert (b.op == traffic.UPDATE).sum() == 100
        assert (b.key >= traffic.ABSENT_LO).sum() == round(0.05 * 1900)
        assert ((b.op == traffic.UPDATE) & (b.key >= traffic.ABSENT_LO)).sum() == 0
    for d in dues:
        assert len(d) == 2000 and d[-1] == pytest.approx(4.0)
        assert np.all(np.diff(d) > 0)
    np.testing.assert_allclose(np.sort(np.diff(dues[0], prepend=0)),
                               np.sort(np.diff(dues[1], prepend=0)))


def _hash_index(seed, n_keys=4000, n_buckets=82):
    struct = registry.structure("hash_table")
    keys, values = traffic.make_data(n_keys, np.random.default_rng([seed, 0]))
    cfg = {"n_buckets": n_buckets, "node_words": 4}
    return keys, struct.build(cfg, keys, values, 1)


def test_depths_are_read_from_the_built_arena():
    """A key's depth is its place in its bucket chain, plus one: the key is
    found by walking that many nodes of the arena from its bucket's head."""
    from repro.core.structures import hash_table

    keys, built = _hash_index(7)
    rows = np.asarray(built["arena"].data)
    heads = np.asarray(built["specs"]["hash"].init_args[0])
    for i in range(0, len(keys), 97):
        ptr = heads[hash_table.hash_fn(np.int64(keys[i]), len(heads))]
        for _ in range(built["depths"][i] - 1):
            ptr = rows[ptr, hash_table.NEXT]
        assert rows[ptr, hash_table.KEY] == keys[i]


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_popularity_weighted_depth_is_the_keys_mean_depth(seed):
    """Depth-matched ranks send every seed the keys' own mean depth, not one
    draw's: the hottest rank sits at the median depth and each pair of hot
    ranks is spread across the depths."""
    keys, built = _hash_index(seed)
    d = built["depths"]
    gen = traffic.Generator({"ops": {"find": 1.0}}, CFG, keys,
                            np.random.default_rng([seed, 1]), seed, d)
    depth_of = dict(zip(keys.tolist(), d.tolist()))
    pmf = np.diff(gen.cdf, prepend=0.0)
    weighted = sum(p * depth_of[int(k)] for p, k in zip(pmf, gen.ranked))
    assert weighted == pytest.approx(d.mean(), rel=0.02)
    assert abs(depth_of[int(gen.ranked[0])] - np.median(d)) <= 1


def test_reference_replays_updates_in_submission_order():
    ref = KeyValueReference(np.array([5, 9], np.int32), np.array([50, 90], np.int32))
    op = np.array([0, 1, 0, 1, 0, 1, 0])
    key = np.array([5, 5, 5, 5, 5, 7, 7])
    val = np.array([0, 51, 0, 52, 0, 70, 0])
    want_val, want_found = ref.replay(op, key, val)
    assert want_val.tolist() == [50, 51, 51, 52, 52, 70, KEY_NOT_FOUND]
    assert want_found.tolist() == [1, 1, 1, 1, 1, 0, 0]


class FakeService:
    """Answers ``per_step`` requests a round; one round stalls."""

    def __init__(self, per_step=32, stall_round=None, stall_s=0.0):
        from repro.core.iterator import STATUS_DONE

        self.done = STATUS_DONE
        self.per_step, self.stall_round, self.stall_s = per_step, stall_round, stall_s
        self.pending, self.rounds, self.in_system = [], 0, []

    def submit(self, r):
        self.pending.append(r)

    def step(self):
        self.in_system.append(len(self.pending))
        if self.rounds == self.stall_round:
            time.sleep(self.stall_s)
        self.rounds += 1
        now = time.perf_counter()
        for r in self.pending[: self.per_step]:
            r.status, r.finish_s, r.result = self.done, now, np.zeros(3, np.int32)
        self.pending = self.pending[self.per_step:]


def _batch(n):
    return traffic.Batch(np.zeros(n, np.int8), np.arange(n, dtype=np.int32),
                         np.zeros(n, np.int32), np.arange(n, dtype=np.int64))


def test_open_loop_latency_runs_from_the_due_time():
    """A stalled step delays the requests due during it: their latency
    counts the stall although they were handed over only after it."""
    client = drive.Client(FakeService(stall_round=5, stall_s=0.3), {"find": "s"}, Spans())
    rng = np.random.default_rng(0)
    win = drive.open_loop(client, _batch(400), 200.0, 2.0, rng)
    lat = drive.latencies_ms(client, win, time.perf_counter())
    lag = np.asarray(client.submit_s) - np.asarray(client.due)
    assert drive.nearest_rank(lat, 0.99) > 200.0
    assert lag.max() > 0.2  # the loop itself ran late during the stall
    calm = drive.Client(FakeService(), {"find": "s"}, Spans())
    win = drive.open_loop(calm, _batch(400), 200.0, 2.0, np.random.default_rng(0))
    assert drive.nearest_rank(drive.latencies_ms(calm, win, time.perf_counter()), 0.99) < 50.0


def test_closed_loop_keeps_64_requests_outstanding():
    svc = FakeService(per_step=32)
    client = drive.Client(svc, {"find": "s"}, Spans())
    drive.closed_loop(client, lambda: _batch(4096), 64, 0.5)
    window_rounds = svc.in_system[:-1]  # the last round is the drain's
    assert len(window_rounds) > 10
    assert set(window_rounds) == {64}


BENCH_JSON = registry.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH_JSON["workloads"]])
def test_every_cell_finds_its_files_by_name(cell):
    w = registry.workload(BENCH_JSON, cell)
    cfg = registry.config(BENCH_JSON, w["config"])
    mix = registry.traffic(w["traffic"])
    assert hasattr(registry.structure(cfg["structure"]), "build")
    assert mix["loop"] in ("open", "closed")
    # one shard per chip; a cell may hold more chips than that (steadiness)
    assert cfg["shards"] == cfg["chips"] <= w["chips"]
    for key in ("guarantees", "source", "reduced", "assumed", "control"):
        assert key in cfg
    e2e = {m["name"] for m in registry.end_to_end_for(BENCH_JSON, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert e2e <= {"setup_s", "p99_ms", "p50_ms", "throughput_rps"}
    assert registry.per_layer_for(BENCH_JSON, cell)


@pytest.mark.parametrize("path", sorted((BENCH_DIR / "configs").glob("*.json")), ids=str)
def test_every_configuration_file_states_what_a_deployment_is(path):
    """Listed or not yet listed, each configuration states its guarantees,
    source, cuts and control, and names a structure module that exists."""
    cfg = json.loads(path.read_text())
    assert cfg["name"] == path.stem
    for key in ("guarantees", "source", "reduced", "assumed", "control", "shards", "chips"):
        assert key in cfg
    assert 1 <= len(cfg["source"]) <= 200
    assert hasattr(registry.structure(cfg["structure"]), "build")


@pytest.mark.parametrize("path", sorted((BENCH_DIR / "traffic").glob("*.json")), ids=str)
def test_every_traffic_file_is_a_mix_the_generator_reads(path):
    mix = json.loads(path.read_text())
    keys, _ = traffic.make_data(1000, np.random.default_rng(0))
    gen = traffic.Generator(mix, CFG, keys, np.random.default_rng(1), 0)
    assert len(gen.batch(100, np.random.default_rng(2)).op) == 100
    assert mix["loop"] in ("open", "closed")
    assert ("rate_rps" in mix) if mix["loop"] == "open" else ("clients" in mix)


@pytest.mark.parametrize(
    "metric",
    sorted({m["name"] for m in BENCH_JSON["per_layer"]}
           | {p.stem for p in (BENCH_DIR / "metrics").glob("*.py")}),
)
def test_every_metric_finds_its_reader_by_name(metric):
    assert callable(registry.metric_module(metric).read)


def test_an_unknown_device_kind_is_an_error():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(registry.LookupFailed):
        registry.peaks("TPU v9 imaginary")


def _run_command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/pulse/bench.py", "--workload",
         "webservice.ycsb-c.closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_command_refuses_to_run_without_a_tpu():
    p = _run_command(CHECKOUT)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_the_command_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "pulse",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_the_result_line_has_the_contract_keys():
    """The keys of the result object, from a CPU run of the harness (not a
    measurement: the command itself refuses the CPU)."""
    out = json.loads(subprocess.run(
        [sys.executable, str(BENCH_DIR / "tests" / "scenarios.py"),
         "webservice.ycsb-c.closed", "sound"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=600, check=True,
    ).stdout.splitlines()[-1])["sound"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"throughput_rps", "setup_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    assert out["correct"] is True
