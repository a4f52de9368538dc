"""Each cell's control fails its limits where the program passes them, at
the cell's own size (on the CPU here; ``bench/control.py`` takes the same
readings on the chip)."""
import json

import pytest
from conftest import ROOT

from bench.control import readings
from bench.harness import compare, spec


@pytest.mark.parametrize("name", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_control_fails_where_the_program_passes(name):
    from repro.api import run
    cell = spec.load_cell(ROOT, name)
    r = readings(cell, 1_234_567_891, run)
    assert compare.judge(r["sound"], cell.limits)[0], r["sound"]
    assert not compare.judge(r["control"], cell.limits)[0], r["control"]
