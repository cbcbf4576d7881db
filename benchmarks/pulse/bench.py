#!/usr/bin/env python3
"""PULSE benchmark: one cell of ``BENCHMARK.json`` on the chips of this machine.

Run from the root of a checkout:

  python3 benchmarks/pulse/bench.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<config>.json``: a deployment of one
structure family, its data scale, skew, chips and guarantees) and a traffic
mix (``traffic/<mix>.json``: operation shares over zipfian keys, open loop
at a fixed Poisson rate or closed loop of a fixed number of clients).  The
run builds the structure from ``--seed`` through the program's own build functions,
serves the traffic through ``PulseService`` over ``PulseEngine`` on the
served arena, and measures for ``--seconds``:

  * set-up: JAX start-up, data and arena from the seed, ``device_put``,
    and warm-up passes until one compiles nothing (``setup_s``);
  * the window: the cell's loop; compiles inside it are counted and printed;
  * after it: every due answer is awaited (up to a minute), the peak device
    memory read, every key the run updated is read back, and every answer is
    compared with the plain reference (``lib/reference.py``) replayed in
    submission order.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces the
window with the JAX profiler and prints the cell's per-layer metrics, each
read by ``metrics/<name>.py``, with the device's busy time and a breakdown.
Earlier lines report the device, the arena, compiles, the paths quanta ran
on, iterations per structure and the generator's lag; the last lines on
standard error give each number compared beside its limit; the last line on
standard output is the result, as one JSON object.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, when the device kind has no peaks in ``lib/peaks.json``, or
when the checkout lacks the program (``src/``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent / "lib"))

import registry  # noqa: E402

CHECKOUT = registry.CHECKOUT
CACHE_DIR = CHECKOUT / ".jax_cache"
WARM_FILLS = (1, 2, 4, 8, 16, 32, 64)  # requests submitted at once, each drained
WARM_LOOP_S = 0.5  # then this long of the cell's own loop
MAX_WARM_PASSES = 6
# request ids: warm-up from 0, the window and the read-back from fixed bases,
# so a seed's window requests (and the values its updates write) do not
# depend on how many warm-up passes ran
WINDOW_ID0 = 1 << 32
READBACK_ID0 = 1 << 33
CLOSED_CHUNK = 4096  # requests drawn at a time for a closed loop


class NoDevice(RuntimeError):
    """No TPU, too few chips, or no peaks for this device kind."""


@dataclasses.dataclass
class Hooks:
    """What tests and the control put in the program's place: a factory for
    the service (``(engine, built, cfg, struct, keys, values) -> object with
    submit/step``) and a wrapper of the engine (``engine -> None``, which
    replaces its ``execute``)."""

    service_factory: object = None
    wrap_execute: object = None


def log(msg: str) -> None:
    print(msg, flush=True)


def setup_jax_cache() -> None:
    """Keep the persistent compilation cache at a fixed path inside the
    checkout, and cache every executable, so only a cell's first run in a
    checkout compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} TPUs, JAX finds {len(devices)}")
    return devices[:chips]


def place_arena(arena, devices):
    """The arena on the cell's chips: whole on one, or range-partitioned over
    a ``mem`` mesh, one shard per chip.  Returns (arena, mesh)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if len(devices) == 1:
        data = jax.device_put(arena.data, devices[0])
        return dataclasses.replace(arena, data=data.block_until_ready()), None
    from repro.distributed.sharding import mem_mesh

    mesh = mem_mesh(len(devices), devices=devices)
    data = jax.device_put(arena.data, NamedSharding(mesh, PartitionSpec("mem", None)))
    return dataclasses.replace(arena, data=data.block_until_ready()), mesh


def run_loop(client, gen, mix: dict, seconds: float, rng, on_close=None):
    import drive

    if mix["loop"] == "open":
        rate = float(mix["rate_rps"])
        batch = gen.batch(max(1, int(round(rate * seconds))), rng)
        return drive.open_loop(client, batch, rate, seconds, rng, on_close)
    if mix["loop"] == "closed":
        return drive.closed_loop(
            client, lambda: gen.batch(CLOSED_CHUNK, rng), int(mix["clients"]),
            seconds, on_close,
        )
    raise ValueError(f"unknown loop {mix['loop']!r}")


def warm_up(client, gen, mix: dict, counter, rng) -> list[dict]:
    """Passes of every batch fill and of the cell's own loop, until a pass
    compiles nothing.  Returns each pass's counts (JAX traces that compile
    nothing, of eager operations, are counted but do not end the warm-up)."""
    passes = []
    for _ in range(MAX_WARM_PASSES):
        before = counter.snapshot()
        for fill in WARM_FILLS:
            client.serve_all(gen.batch(fill, rng), time.perf_counter() + 120)
        run_loop(client, gen, mix, WARM_LOOP_S, rng)
        client.drain(time.perf_counter() + 120)
        delta = counter.delta(before, counter.snapshot())
        passes.append(delta)
        if not counter.compiles(delta):
            break
    return passes


def quanta_by_path(service) -> dict:
    """The service's count of quanta per ``ExecResult.path`` (none for a
    control, which runs no quanta)."""
    m = getattr(service, "metrics", None)
    return dict(m.quanta_by_path) if m is not None else {}


def answers(client, struct) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(value, found, done) of every logged request."""
    from repro.core.iterator import STATUS_DONE
    from traffic import OPS

    n = len(client.log)
    val = np.zeros(n, np.int64)
    found = np.zeros(n, np.int64)
    done = np.zeros(n, bool)
    for j, r in enumerate(client.log):
        if r.status != STATUS_DONE or r.result is None:
            continue
        done[j] = True
        vcol, fcol = struct.ANSWER[OPS[client.op[j]]]
        if vcol is not None:
            val[j] = int(r.result[vcol])
        found[j] = int(r.result[fcol])
    return val, found, done


@dataclasses.dataclass
class Served:
    """A cell set up and warm: the service over the engine over the served
    arena, the client that logs every request, and the seed's data."""

    keys: np.ndarray
    values: np.ndarray
    struct: object
    built: dict
    arena: object
    service: object
    client: object
    gen: object
    spans: object
    counter: object
    gc_pauses: object
    rng: object  # the window's draws
    setup: dict


def set_up(cfg: dict, mix: dict, *, seed: int, devices: list,
           hooks: Hooks | None = None) -> Served:
    """Data and structure from the seed, the arena on the chips, the service,
    and warm-up until a pass compiles nothing."""
    import drive
    import traffic
    from repro.core.arena import NULL
    from repro.core.engine import PulseEngine
    from repro.serving.traversal_service import PulseService
    from spans import CompileCounter, GcPauses, Spans, instrument_engine

    hooks = hooks or Hooks()
    counter = CompileCounter()
    gc_pauses = GcPauses()
    data_rng, rank_rng, warm_rng, win_rng = (
        np.random.default_rng([seed, k]) for k in range(4)
    )
    t0 = time.perf_counter()
    keys, values = traffic.make_data(int(cfg["n_keys"]), data_rng)
    struct = registry.structure(cfg["structure"])
    built = struct.build(cfg, keys, values, int(cfg["shards"]))
    t_build = time.perf_counter() - t0
    # one shard per chip; a cell may hold more chips than its shards (the
    # whole host, for steadiness), and those stay idle
    arena, mesh = place_arena(built["arena"], devices[: int(cfg["shards"])])
    engine = PulseEngine(arena, mesh=mesh)
    if hooks.wrap_execute is not None:
        hooks.wrap_execute(engine)
    spans = Spans()
    instrument_engine(engine, spans, NULL)
    if hooks.service_factory is None:
        service = PulseService(engine, built["specs"])
    else:
        service = hooks.service_factory(engine, built, cfg, struct, keys, values)
    client = drive.Client(service, built["op_spec"], spans)
    gen = traffic.Generator(mix, cfg, keys, rank_rng, seed, built.get("depths"))
    t1 = time.perf_counter()
    warm = warm_up(client, gen, mix, counter, warm_rng)
    setup = {"build_s": t_build, "place_s": t1 - t0 - t_build,
             "warm_s": time.perf_counter() - t1, "warm_passes": warm,
             "warm_requests": len(client.log)}
    return Served(keys, values, struct, built, arena, service, client, gen, spans,
                  counter, gc_pauses, win_rng, setup)


def read_back_and_check(sv: Served) -> dict:
    """Serve a find of every key the run updated, then compare every logged
    answer with the reference replayed in submission order.  Returns the
    numbers compared, each with its limit, and the mask of wrong requests."""
    import drive
    import reference
    import traffic

    client = sv.client
    upd = [client.log[j].query for j in range(len(client.log))
           if client.op[j] == traffic.UPDATE]
    sv.gen.next_id = READBACK_ID0
    rb_first = len(client.log)
    if upd:
        client.serve_all(sv.gen.readback(upd), time.perf_counter() + drive.DRAIN_LIMIT_S)
    if hasattr(sv.service, "close"):
        sv.service.close()
    t0 = time.perf_counter()
    ref = reference.KeyValueReference(sv.keys, sv.values)
    op = np.asarray(client.op, np.int8)
    qkeys = np.asarray([r.query for r in client.log], np.int64)
    qvals = np.asarray([r.value for r in client.log], np.int64)
    want_val, want_found = ref.replay(op, qkeys, qvals)
    got_val, got_found, done = answers(client, sv.struct)

    def cmp(sl):
        return reference.compare(op[sl], want_val[sl], want_found[sl],
                                 got_val[sl], got_found[sl], done[sl])

    main, rb = cmp(slice(0, rb_first)), cmp(slice(rb_first, len(client.log)))
    return {
        "compared": {
            "not_served": {"value": main["not_served"], "limit": 0},
            "wrong_answers": {"value": main["wrong_answers"], "limit": 0},
            "readback_wrong": {
                "value": rb["not_served"] + rb["wrong_answers"], "limit": 0,
            },
        },
        "wrong": main["wrong_mask"],
        "readback": len(client.log) - rb_first,
        "check_s": time.perf_counter() - t0,
    }


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, devices: list, peak: dict,
             hooks: Hooks | None = None, t_start: float = T_START) -> dict:
    """One run of one cell; returns the result object and what the earlier
    lines print."""
    import jax

    import devtrace
    import drive

    sv = set_up(cfg, mix, seed=seed, devices=devices, hooks=hooks)
    client, spans, service, counter = sv.client, sv.spans, sv.service, sv.counter
    chips = len(devices)

    # ------------------------------ the window ------------------------------
    tmp = tempfile.mkdtemp(prefix="pulse_bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    sv.gen.next_id = WINDOW_ID0
    c0 = counter.snapshot()
    g0 = len(sv.gc_pauses.pauses_ms)
    q0 = quanta_by_path(service)
    window_ann = jax.profiler.TraceAnnotation("bench.window")
    closed_at = {}

    def on_close():
        closed_at["quanta"] = quanta_by_path(service)
        closed_at["backlog"] = len(client.outstanding)
        closed_at["gc"] = sv.gc_pauses.summary(g0)
        spans.on = False
        window_ann.__exit__(None, None, None)

    setup_s = time.perf_counter() - t_start
    spans.on = trace
    window_ann.__enter__()
    win = run_loop(client, sv.gen, mix, seconds, sv.rng, on_close)
    give_up = time.perf_counter()
    compiles = counter.delta(c0, counter.snapshot())
    if trace:
        jax.profiler.stop_trace()
    mem_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    chk = read_back_and_check(sv)
    compared = chk["compared"]
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    in_win = np.arange(win.first, win.stop)
    wrong = chk["wrong"][in_win]
    failed = int(wrong.sum())

    # -------------------------------- metrics -------------------------------
    lat = drive.latencies_ms(client, win, give_up)
    finish = np.asarray([client.log[j].finish_s for j in in_win])
    served_in_window = int(((finish >= 0) & (finish <= win.end) & ~wrong).sum())
    e2e_values = {
        "setup_s": setup_s,
        "p99_ms": drive.nearest_rank(lat, 0.99),
        "p50_ms": drive.nearest_rank(lat, 0.50),
        "throughput_rps": served_in_window / seconds,
    }
    lag_ms = (np.asarray(client.submit_s)[in_win] - np.asarray(client.due)[in_win]) * 1e3
    iters = {}
    for name in sorted({client.log[j].structure for j in in_win}):
        its = [client.log[j].iters for j in in_win if client.log[j].structure == name]
        iters[name] = float(np.mean(its))
    quanta = {
        k: v - q0.get(k, 0) for k, v in closed_at.get("quanta", {}).items()
        if v - q0.get(k, 0)
    }
    arena = sv.arena
    record = {
        "device": devices[0],
        "arena": {"rows": int(arena.capacity), "node_words": int(arena.node_words),
                  "bytes": int(arena.data.nbytes), "shards": int(arena.num_shards),
                  **sv.built["info"]},
        "setup": {**sv.setup, "setup_s": setup_s},
        "window": {"loop": win.loop, "seconds": seconds, "requests": win.stop - win.first,
                   "compiles": compiles, "quanta_by_path": quanta,
                   "backlog_at_close": closed_at.get("backlog"),
                   "gc_full_collections": closed_at.get("gc"),
                   "mean_iters": iters, "served_in_window": served_in_window},
        "gen_lag_ms": {"p50": float(np.median(lag_ms)),
                       "p99": drive.nearest_rank(lag_ms, 0.99),
                       "max": float(lag_ms.max())},
        "latency_ms": {"p50": e2e_values["p50_ms"], "p99": e2e_values["p99_ms"],
                       "max": float(lat.max())},
        "check_s": chk["check_s"], "readback": chk["readback"],
    }

    if not trace:
        metrics = {
            m["name"]: {"value": e2e_values[m["name"]], "unit": m["unit"]}
            for m in registry.end_to_end_for(bench, cell["name"])
        }
        device_extra, bd = {}, None
    else:
        files = sorted(Path(tmp).rglob("*.xplane.pb"))
        ev = devtrace.load_events(str(files[0])) if files else None
        shutil.rmtree(tmp, ignore_errors=True)
        if ev is not None:  # the chips that hold the arena
            ev = devtrace.only_devices(ev, [d.id for d in devices[: int(cfg["shards"])]])
        if ev is None or not ev["devices"]:
            raise RuntimeError("the trace holds no device plane")
        view = RunView(
            spans=spans, trace=ev, chips=int(cfg["shards"]), peak=peak,
            row_bytes=int(arena.node_words) * 4, lag_ms=lag_ms,
        )
        metrics = {}
        for m in registry.per_layer_for(bench, cell["name"]):
            v = registry.metric_module(m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        lo, hi = devtrace.window(ev)
        device_extra = {"busy_s": devtrace.busy_s_mean(ev), "window_s": (hi - lo) / 1e9}
        bd = devtrace.breakdown(ev)
    dev = devices[0]
    result = {
        "correct": bool(correct),
        "attempted": int(win.stop - win.first),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": chips,
                   "memory_peak_bytes": int(mem_peak), **device_extra},
    }
    if bd is not None:
        result["breakdown"] = bd
    result["compared"] = compared
    return {"result": result, "record": record}


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader gets: the window's spans, the reduced
    device trace of the chips that hold the arena, the number of those chips
    and their peaks, the arena's row bytes, and the generator's lag."""

    spans: object
    trace: dict | None
    chips: int
    peak: dict
    row_bytes: int
    lag_ms: np.ndarray


def print_run(out: dict) -> None:
    rec, res = out["record"], out["result"]
    dev = rec["device"]
    log(f"device platform={dev.platform} kind={dev.device_kind} count={res['device']['count']}")
    log("arena " + json.dumps(rec["arena"]))
    log("setup " + json.dumps(rec["setup"]))
    log("window " + json.dumps(rec["window"]))
    log(f"compiles_in_window {json.dumps(rec['window']['compiles'])}")
    log("generator_lag_ms " + json.dumps(rec["gen_lag_ms"]))
    log("latency_ms " + json.dumps(rec["latency_ms"]))
    log(f"check readback_requests={rec['readback']} check_s={rec['check_s']:.4f}")
    for name, c in res["compared"].items():
        print(f"compared {name}={c['value']} limit={c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        bench = registry.load_benchmark()
        cell = registry.workload(bench, args.workload)
        cfg = registry.config(bench, cell["config"])
        mix = registry.traffic(cell["traffic"])
    except registry.LookupFailed as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (CHECKOUT / "src" / "repro").is_dir():
        print(f"bench: no program at {CHECKOUT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(CHECKOUT / "src"))
    setup_jax_cache()
    try:
        devices = find_devices(int(cell["chips"]))
        peak = registry.peaks(devices[0].device_kind)
    except (NoDevice, registry.LookupFailed) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    out = run_cell(
        bench, cell, cfg, mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, peak=peak,
    )
    print_run(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
