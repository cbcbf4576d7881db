"""``correct`` on whole CPU runs at a tiny size (``scenarios.py``): true for
the program as it is, false for the control and for each fault the cell can
have, planted under the timed path."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCENARIOS = Path(__file__).parent / "scenarios.py"
FAULTS = ["state_unchanged", "half_batch", "answer_altered"]
CELLS = {
    "webservice.ycsb-c.closed": ["sound", "control", *FAULTS],
    # the same cell with its arena over four virtual chips: the exchange
    "webservice.ycsb-c.closed@4": ["sound", "exchange_left_out"],
}
_RUNS: dict = {}


def _results(cell: str) -> dict:
    if cell not in _RUNS:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, str(SCENARIOS), cell, *CELLS[cell]],
            env=env, capture_output=True, text=True, timeout=900,
        )
        assert p.returncode == 0, p.stderr[-3000:]
        _RUNS[cell] = json.loads(p.stdout.splitlines()[-1])
    return _RUNS[cell]


@pytest.mark.parametrize(
    "cell,scenario", [(c, s) for c, ss in CELLS.items() for s in ss]
)
def test_correct_is_true_only_for_the_program_as_it_is(cell, scenario):
    res = _results(cell)[scenario]
    assert res["correct"] is (scenario == "sound"), res["compared"]
    assert res["attempted"] > 0
    if scenario != "sound":
        assert any(c["value"] > c["limit"] for c in res["compared"].values())
