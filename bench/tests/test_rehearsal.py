"""A CPU rehearsal of every cell of ``BENCHMARK.json`` at its own size: one
short window, the reference and the comparison, and the metrics a run
without a device reports."""
import time

import pytest
from conftest import ROOT

from bench.harness import spec
from bench.harness.cell import run_cell


def cells():
    import json
    return [w["name"] for w in
            json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", cells())
def test_one_short_request_per_cell(name, tmp_path):
    cell = spec.load_cell(ROOT, name)
    traced = name.endswith(".fluid")
    result, checks = run_cell(cell, seed=3_000_000_019, seconds=0,
                              traced=traced, rehearse=True,
                              t_start=time.perf_counter(),
                              profile_dir=tmp_path)
    assert result["correct"], checks
    assert result["attempted"] == 1 and result["failed"] == 0
    metrics = cell.per_layer if traced else cell.end_to_end
    want = {m["name"] for m in metrics if m["source"] != "device_trace"}
    assert set(result["metrics"]) == want
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
