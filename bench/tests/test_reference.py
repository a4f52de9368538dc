"""The plain references agree with the program where they must: the same
phases and routes at the cells' sizes, the same packet-level answer to the
bit and the same fluid answer to float32 rounding on a small deployment."""
import json

import pytest
from conftest import ROOT

from bench.reference import deployment, fluid, packet


def program(cfg, ranks):
    from repro.api import training_scenario
    return training_scenario(**cfg["preset"], chaos=[
        {"kind": "straggler", "ranks": ranks, "factor": 1.5}])


@pytest.mark.parametrize("name", ["gpt13b-128", "moe8x13b-128"])
def test_phases_and_routes_match_the_program(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    ranks = deployment.straggler_ranks(2**31 + 5, 2, 128)
    scn = program(cfg, ranks)
    ours = deployment.training_phases(cfg, {r: 1.5 for r in ranks})
    theirs = scn.build_phases()
    assert len(ours) == len(theirs)
    for a, b in zip(ours, theirs):
        assert (a.deps, a.compute) == (b.deps, b.compute), a.name
        assert [(f.fid, f.src, f.dst, f.size, f.tag) for f in a.flows] == \
            [(f.fid, f.src, f.dst, f.size, f.tag) for f in b.flows], a.name
    fab, topo = deployment.Fabric.from_config(cfg), scn.build_topology()
    for ph in ours:
        for f in ph.flows:
            assert fab.route(f.src, f.dst, f.fid) == \
                topo.route(f.src, f.dst, f.fid)


def test_straggler_draw_is_fixed_by_the_seed():
    a = deployment.straggler_ranks(4_000_000_007, 2, 128)
    assert a == deployment.straggler_ranks(4_000_000_007, 2, 128)
    assert len(set(a)) == 2 and all(0 <= r < 128 for r in a)


def test_packet_reference_is_the_program_packet_loop(small_config):
    from repro.api import run
    ranks = [3, 9]
    ours = packet.PacketReference(
        deployment.Fabric.from_config(small_config),
        deployment.training_phases(small_config, {r: 1.5 for r in ranks}),
        small_config["sim"], small_config["hpcc"]).run()
    theirs = run(program(small_config, ranks), backend="packet")
    assert ours["fct"] == theirs.fcts
    assert ours["iteration_time"] == theirs.iteration_time
    assert ours["events"] == theirs.events_processed


def test_fluid_reference_agrees_with_the_program(small_config):
    from repro.api import run
    ranks = [3, 9]
    ours = fluid.iteration(
        deployment.Fabric.from_config(small_config),
        deployment.training_phases(small_config, {r: 1.5 for r in ranks}),
        small_config["fluid"])
    theirs = run(program(small_config, ranks), backend="fluid")
    assert set(ours["fct"]) == set(theirs.fcts)
    for fid, v in ours["fct"].items():
        assert theirs.fcts[fid] == pytest.approx(v, rel=1e-6)
