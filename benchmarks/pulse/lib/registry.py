"""Find a cell's configuration, traffic mix, structure module and per-layer
metrics by the names in ``BENCHMARK.json``.

Every configuration, mix, structure family and metric is a file of its own:

  configs/<config>.json        the file named by the config entry's ``file``
  traffic/<mix>.json           one traffic mix
  structures/<structure>.py    one structure family (``cfg["structure"]``)
  metrics/<metric>.py          one per-layer metric; ``<base>.lat`` and
                               ``<base>.tput`` share ``metrics/<base>.py``

so a later cell adds files and ``BENCHMARK.json`` entries and edits nothing.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]
BENCHMARK_JSON = CHECKOUT / "BENCHMARK.json"


class LookupFailed(LookupError):
    """A name in BENCHMARK.json (or on the command line) has no file."""


def load_benchmark(path: Path = BENCHMARK_JSON) -> dict:
    if not path.is_file():
        raise LookupFailed(f"no {path.name} at {path.parent}")
    return json.loads(path.read_text())


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise LookupFailed(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    entry = _entry(bench["configs"], name, "config")
    path = CHECKOUT / entry["file"]
    if not path.is_file():
        raise LookupFailed(f"config {name!r}: no file {entry['file']}")
    return json.loads(path.read_text())


def traffic(name: str) -> dict:
    path = BENCH_DIR / "traffic" / f"{name}.json"
    if not path.is_file():
        raise LookupFailed(f"no traffic mix file traffic/{name}.json")
    return json.loads(path.read_text())


def _module(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def structure(name: str):
    path = BENCH_DIR / "structures" / f"{name}.py"
    if not path.is_file():
        raise LookupFailed(f"no structure module structures/{name}.py")
    return _module(path, f"pulse_bench_structure_{name}")


def metric_module(name: str):
    """``metrics/<name>.py``, else the file of the name before its first dot
    (the ``.lat``/``.tput`` forms of one quantity share their reader)."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH_DIR / "metrics" / f"{stem}.py"
        if path.is_file():
            return _module(path, "pulse_bench_metric_" + stem.replace("-", "_"))
    raise LookupFailed(f"no metric reader metrics/{name}.py")


def per_layer_for(bench: dict, cell: str) -> list[dict]:
    """The per-layer metrics this cell reports: those listing it under
    ``workloads``, and those without the key whose ``moves`` the cell
    reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if (cell in cells) if cells is not None else m["moves"] in e2e:
            out.append(m)
    return out


def end_to_end_for(bench: dict, cell: str) -> list[dict]:
    return [
        m for m in bench["end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    ]


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads((BENCH_DIR / "lib" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise LookupFailed(
            f"device kind {device_kind!r} is not in lib/peaks.json "
            f"(known: {sorted(table['devices'])})"
        )
    return table["devices"][device_kind]
