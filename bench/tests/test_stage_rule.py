"""A configuration names its own stage rule, a new file under
``bench/reference/``, and the harness builds the cell's phases from it.

The fixture ``data/uneven-16.json`` (TP2-DP4-PP2, EP domains of 2) names
``data/uneven_stages.py``: stage 0 holds 1 dense and 2 MoE layers, stage 1
holds 4 MoE layers.  Ranks are ``t + 2*(d + 4*s)``, so stage 0's DP groups
are [0, 2, 4, 6] and [1, 3, 5, 7], its EP domains [0, 2], [4, 6], [1, 3],
[5, 7], and its expert-DP groups [0, 4], [2, 6], [1, 5], [3, 7].
"""
import dataclasses
import importlib.util
import json
import shutil
import subprocess
import sys

import pytest
from conftest import DATA, ROOT

from bench.harness import spec
from bench.harness.cell import variant
from bench.reference import deployment

CELL = "uneven-16.fluid"


def fixture_config() -> dict:
    return json.loads((DATA / "uneven-16.json").read_text())


def tree(tmp_path, bench_json, config: dict):
    """A checkout whose ``bench/`` is the repo's plus new files only: the
    fixture's configuration, its rule, and a limits file for its cell."""
    bench = tmp_path / "bench"
    bench.mkdir()
    for entry in (ROOT / "bench").iterdir():
        if entry.name not in ("configs", "reference", "limits"):
            (bench / entry.name).symlink_to(entry)
            continue
        (bench / entry.name).mkdir()
        for f in entry.iterdir():
            (bench / entry.name / f.name).symlink_to(f)
    (bench / "configs" / "uneven-16.json").write_text(json.dumps(config))
    shutil.copy(DATA / "uneven_stages.py", bench / "reference")
    shutil.copy(ROOT / "bench" / "limits" / "gpt13b-128.fluid.json",
                bench / "limits" / f"{CELL}.json")
    doc = dict(bench_json)
    doc["configs"] = bench_json["configs"] + [
        {"name": "uneven-16", "source": "test", "file":
         "bench/configs/uneven-16.json", "reduced": [], "why": "test"}]
    doc["workloads"] = bench_json["workloads"] + [
        {"name": CELL, "config": "uneven-16", "traffic": "fluid-iter",
         "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp_path


def rule():
    """The fixture's ``stage_loads``, loaded from its file."""
    name = "uneven_stages_fixture"
    if name not in sys.modules:
        spec_ = importlib.util.spec_from_file_location(
            name, DATA / "uneven_stages.py")
        sys.modules[name] = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(sys.modules[name])
    return sys.modules[name].stage_loads


def sig(phases):
    return [(p.name, p.deps, p.compute,
             [(f.fid, f.src, f.dst, f.size, f.tag) for f in p.flows])
            for p in phases]


def test_uneven_stages_by_hand():
    cfg = fixture_config()
    phases = deployment.gpipe_phases(cfg, rule()(cfg), {9: 1.5})
    by = {p.name: p for p in phases}
    assert list(by) == ["fwd.m0.s0", "fwd.m0.s1", "fwd.m1.s0", "fwd.m1.s1",
                        "bwd.m0.s1", "bwd.m0.s0", "bwd.m1.s1", "bwd.m1.s0",
                        "dp.s0", "edp.s0", "dp.s1", "edp.s1"]

    def sizes(name, tag):
        return sorted({f.size for f in by[name].flows if f.tag.startswith(tag)})

    def count(name, tag):
        return sum(f.tag.startswith(tag) for f in by[name].flows)

    # 3 layers of 0.5 ms on stage 0; 4 on stage 1, stretched by rank 9
    assert by["fwd.m0.s0"].compute == pytest.approx(1.5e-3, rel=1e-15)
    assert by["bwd.m1.s0"].compute == pytest.approx(3e-3, rel=1e-15)
    assert by["fwd.m1.s1"].compute == pytest.approx(3e-3, rel=1e-15)
    assert by["bwd.m0.s1"].compute == pytest.approx(6e-3, rel=1e-15)
    for mb in (0, 1):
        # 4 EP domains of 2 a stage: 8 flows of a2a_bytes / 2 each way
        for d in ("fwd", "bwd"):
            assert count(f"{d}.m{mb}.s0", "ep.") == 8
            assert sizes(f"{d}.m{mb}.s0", "ep.") == [2 * 4096 / 2]
            assert count(f"{d}.m{mb}.s1", "ep.") == 8
            assert sizes(f"{d}.m{mb}.s1", "ep.") == [4 * 4096 / 2]
        # 8 ranks a stage: activations forward out of 0, back out of 1
        assert count(f"fwd.m{mb}.s0", "pp.") == 8
        assert count(f"fwd.m{mb}.s1", "pp.") == 0
        assert count(f"bwd.m{mb}.s1", "pp.") == 8
        assert count(f"bwd.m{mb}.s0", "pp.") == 0
        assert sizes(f"fwd.m{mb}.s0", "pp.") == [8192.0]
        assert sizes(f"bwd.m{mb}.s1", "pp.") == [8192.0]
    # DP rings of 4: (n - 1) / n of the bytes each way; expert rings of 2
    assert (count("dp.s0", "dp.s0"), sizes("dp.s0", "dp")) == (16, [2250.0])
    assert (count("dp.s1", "dp.s1"), sizes("dp.s1", "dp")) == (16, [3000.0])
    assert (count("edp.s0", "edp.s0"), sizes("edp.s0", "edp")) == (16, [3000.0])
    assert (count("edp.s1", "edp.s1"), sizes("edp.s1", "edp")) == (16, [6000.0])
    assert {(f.src, f.dst) for f in by["edp.s0"].flows} == {
        (0, 4), (4, 0), (2, 6), (6, 2), (1, 5), (5, 1), (3, 7), (7, 3)}
    assert {(f.src, f.dst) for f in by["fwd.m0.s1"].flows
            if f.tag.startswith("ep.")} == {
        (8, 10), (10, 8), (12, 14), (14, 12),
        (9, 11), (11, 9), (13, 15), (15, 13)}
    index = {p.name: i for i, p in enumerate(phases)}
    for s in (0, 1):
        done = [index[f"bwd.m{mb}.s{s}"] for mb in (0, 1)]
        assert by[f"dp.s{s}"].deps == done
        assert by[f"edp.s{s}"].deps == done
    assert by["bwd.m1.s0"].deps == [index["fwd.m1.s1"], index["bwd.m1.s1"],
                                    index["bwd.m0.s0"]]
    fids = [f.fid for p in phases for f in p.flows]
    assert fids == list(range(len(fids)))


@pytest.mark.parametrize("why", ["no expert bytes", "expert groups of one"])
def test_no_expert_ring(why):
    cfg = fixture_config()
    loads = rule()(cfg)
    if why == "no expert bytes":
        loads = [dataclasses.replace(load, expert_grad_bytes=0.0)
                 for load in loads]
    else:
        cfg["parallel"]["ep_domain"] = 4    # one EP domain per DP group
    phases = deployment.gpipe_phases(cfg, loads, {})
    assert [p.name for p in phases][-2:] == ["dp.s0", "dp.s1"]
    assert not any(f.tag.startswith("edp") for p in phases for f in p.flows)


def test_stage_count_must_match_the_pipeline():
    cfg = fixture_config()
    with pytest.raises(ValueError, match="stage loads"):
        deployment.gpipe_phases(cfg, rule()(cfg)[:1], {})


def test_a_cell_whose_rule_is_a_new_file(tmp_path, bench_json):
    root = tree(tmp_path, bench_json, fixture_config())
    cell = spec.load_cell(root, CELL)
    cfg = cell.config
    assert cell.stage_loads(cfg) == rule()(cfg)
    scn, phases, ranks = variant(cell, 3_000_000_029)
    slow = {r: cell.mix["variant"]["stragglers"]["factor"] for r in ranks}
    assert sig(phases) == sig(deployment.gpipe_phases(cfg, rule()(cfg), slow))
    assert "edp.s1" in [p.name for p in phases]


@pytest.mark.parametrize("name", ["no-such-rule", "../harness/spec", "packet",
                                  3])
def test_unknown_rule_is_refused(tmp_path, bench_json, name):
    root = tree(tmp_path, bench_json, {**fixture_config(), "stage_rule": name})
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, CELL)


def test_cells_without_a_rule_take_the_table1_rule(bench_json):
    for w in bench_json["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.stage_loads is deployment.stage_loads
        scn, phases, ranks = variant(cell, 2**31 + 11)
        slow = {r: cell.mix["variant"]["stragglers"]["factor"]
                for r in ranks}
        assert sig(phases) == sig(deployment.training_phases(cell.config,
                                                             slow))


@pytest.mark.parametrize("path", sorted(
    (ROOT / "bench" / "reference").glob("*.py")) + [DATA / "uneven_stages.py"],
    ids=lambda p: p.name)
def test_a_reference_imports_nothing_of_the_program(path):
    code = ("import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('m', {str(path)!r})\n"
            "sys.modules['m'] = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(sys.modules['m'])\n"
            "print(sorted(m for m in sys.modules if m.split('.')[0] == 'repro'))")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        env={"PYTHONPATH": f"{ROOT}:{ROOT / 'src'}", "JAX_PLATFORMS": "cpu"},
        timeout=120, check=True)
    assert out.stdout.strip() == "[]"
