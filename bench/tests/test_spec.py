"""The harness finds every file by name and refuses what it cannot find;
``BENCHMARK.json`` keeps to the shape later PRs rely on."""
import json
import re

import pytest
from conftest import ROOT

from bench.harness import spec

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_every_cell_loads_its_files(bench_json):
    for w in bench_json["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["backend"] in ("wormhole", "hybrid", "fluid")
        assert cell.limits, "a cell without limits could never be correct"
        assert set(cell.readers) == {m["name"] for m in
                                     cell.end_to_end + cell.per_layer}
        assert "setup_s" in cell.readers
        assert {"iter_s", "iter_s.hybrid"} & set(cell.readers)
        moved = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in moved for m in cell.per_layer)


@pytest.mark.parametrize("name", ["no-such-cell", "../BENCHMARK",
                                  "gpt13b-128"])
def test_unknown_cell_is_refused(name):
    with pytest.raises(spec.SpecError):
        spec.load_cell(ROOT, name)


@pytest.mark.parametrize("folder,name", [("configs", "no-such-config"),
                                         ("traffic", "../configs/x"),
                                         ("metrics", "no.such.metric")])
def test_unknown_file_is_refused(folder, name):
    with pytest.raises(spec.SpecError):
        if folder == "metrics":
            spec.load_reader(ROOT, name)
        else:
            spec._json(ROOT, folder, name)


def test_cell_with_missing_limits_is_refused(tmp_path, bench_json):
    (tmp_path / "bench").symlink_to(ROOT / "bench")
    doc = dict(bench_json)
    doc["workloads"] = bench_json["workloads"] + [
        {"name": "gpt13b-128.packet", "config": "gpt13b-128",
         "traffic": "wormhole-cold", "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(spec.SpecError, match="limits"):
        spec.load_cell(tmp_path, "gpt13b-128.packet")


def test_benchmark_json_shape(bench_json):
    assert set(bench_json) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in bench_json["configs"]]
    names += [w["name"] for w in bench_json["workloads"]]
    names += [m["name"] for m in bench_json["end_to_end"]
              + bench_json["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    cells = {w["name"] for w in bench_json["workloads"]}
    for c in bench_json["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"] == f"bench/configs/{c['name']}.json"
        for key in c["reduced"]:
            assert key in cfg and cfg[key] != cfg["published"][key]
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    assert {m["name"] for m in bench_json["end_to_end"]} >= {"setup_s",
                                                           "iter_s"}
    for m in bench_json["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench_json["end_to_end"]}


def test_stated_guarantee_is_the_limit(bench_json):
    """Where the configuration states a limit, the cell's limit is it."""
    for w in bench_json["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        stated = cell.config["guarantee"]["fct_err_mean"].get(
            cell.mix["backend"])
        if stated is not None:
            assert cell.limits["fct_err_mean"]["limit"] == stated
