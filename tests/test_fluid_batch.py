"""The fluid backend solves every phase of an iteration in one device
program (``converged_phase_rates`` over ``fluid_run_phases``), and gives
the FCTs the per-phase solve (``fluid_converged_rates``) gives; its
``run_batch`` solves a sweep's scenarios the same way."""
import numpy as np
import pytest

from repro.api import Scenario, get_engine, run, training_scenario
from repro.net.flows import FlowSpec
from repro.net.fluid_jax import (FluidScenario, converged_phase_rates,
                                 fluid_converged_rates, fluid_run,
                                 fluid_run_phases, pad_scenarios)
from test_spans import traced_run

STEPS = 200
WINDOW = max(8, STEPS // 10)


def per_phase(scn, dt=None):
    """FCTs and iteration time with one ``fluid_converged_rates`` solve
    per phase, the DAG scheduled as ``FluidEngine.run`` schedules it."""
    topo = scn.build_topology()
    phases = scn.build_phases()
    fcts, done = {}, [0.0] * len(phases)
    for i, ph in enumerate(phases):
        start = max((done[d] for d in set(ph.deps)), default=0.0) + ph.compute
        end = start
        if ph.flows:
            fs = FluidScenario.from_flows(
                topo, [(f.fid, f.src, f.dst, f.size) for f in ph.flows])
            rates = fluid_converged_rates(fs, steps=STEPS, dt=dt)["rates"]
            for f, rate in zip(ph.flows, rates):
                fcts[f.fid] = f.size / max(float(rate), 1e3)
                end = max(end, start + fcts[f.fid])
        done[i] = end
    return fcts, max(done)


def first_phase(moe=False):
    scn = training_scenario(n_gpus=128, moe=moe)
    topo = scn.build_topology()
    ph = next(p for p in scn.build_phases() if p.flows)
    return FluidScenario.from_flows(
        topo, [(f.fid, f.src, f.dst, f.size) for f in ph.flows])


@pytest.mark.parametrize("dt", [None, 1e-5], ids=["dt-median-rtt", "dt-1e-5"])
@pytest.mark.parametrize("moe", [False, True], ids=["gpt128", "moe128"])
def test_batched_run_matches_per_phase_solves(moe, dt):
    scn = training_scenario(n_gpus=128, moe=moe)
    res = run(scn, backend="fluid", dt=dt)
    fcts, iteration = per_phase(scn, dt)
    # padding adds exact zeros: on the CPU every FCT is the same float
    assert res.fcts == fcts
    assert res.iteration_time == iteration
    n_solved = sum(1 for ph in scn.build_phases() if ph.flows)
    assert res.events_processed == STEPS * n_solved
    assert 0.0 <= res.extras["fluid_pad_share"] < 1.0


@pytest.mark.parametrize("extra_flows,extra_links",
                         [(0, 0), (7, 0), (0, 9), (64, 32)])
def test_padding_leaves_a_phase_unchanged(extra_flows, extra_links):
    fs = first_phase()
    F, L = fs.incidence.shape
    dt = np.float32(np.median(fs.base_rtt))
    # a second phase as wide as the padding asks for forces the first's
    # rows and columns out to that width
    wide = FluidScenario(
        incidence=np.ones((F + extra_flows, L + extra_links), np.float32),
        line_rate=np.full(F + extra_flows, 12.5e9),
        base_rtt=np.full(F + extra_flows, 4e-6), size=np.zeros(F + extra_flows),
        link_bw=np.full(L + extra_links, 12.5e9))
    M, line, rtt0, bw = pad_scenarios([fs, wide])
    unbounded = np.full(line.shape, np.inf)
    win = np.asarray(fluid_run_phases(M, line, rtt0, unbounded, bw,
                                      np.array([dt, dt], np.float32),
                                      steps=STEPS, window=WINDOW))
    alone = np.asarray(fluid_run(
        fs.incidence, fs.line_rate, fs.base_rtt, np.full(F, np.inf),
        fs.link_bw, float(dt), STEPS)["rate_hist"])[-WINDOW:]
    assert win.shape == (2, WINDOW, F + extra_flows)
    np.testing.assert_array_equal(win[0, :, :F], alone)


@pytest.mark.parametrize("steps", [5, 60, 400])
def test_one_phase_matches_its_own_solve(steps):
    """A single phase is a batch of one, and a run shorter than the
    trailing window averages every step, as the per-phase solve does."""
    fs = first_phase(moe=True)
    (rates,), share = converged_phase_rates([fs], steps=steps)
    # XLA may contract a batch of one along another path than the unbatched
    # matmul (last-bit differences at 128 flows): float32 rounding only
    np.testing.assert_allclose(
        rates, fluid_converged_rates(fs, steps=steps)["rates"], rtol=1e-6)
    assert share == 0.0


def test_one_launch_and_one_fetch_per_run(tmp_path):
    res, spans = traced_run("fluid", tmp_path)
    count = {}
    for name, *_ in spans:
        count[name] = count.get(name, 0) + 1
    assert count["repro.fluid.launch"] == 1
    assert count["repro.fluid.fetch"] == 1
    assert count["repro.fluid.converge"] == 1
    assert count["repro.fluid.build"] == res.events_processed // STEPS > 1


def test_straggler_variants_share_one_compile():
    base = training_scenario(n_gpus=128)
    run(base, backend="fluid")                     # compile the shape
    before = fluid_run_phases._cache_size()
    for ranks in ([3, 70], [15, 100]):
        run(base.variant(chaos=[{"kind": "straggler", "ranks": ranks,
                                 "factor": 1.5}]), backend="fluid")
    assert fluid_run_phases._cache_size() == before


def test_pad_share_counts_the_padding():
    small, big = first_phase(), first_phase(moe=True)
    rates, share = converged_phase_rates([small, big], steps=STEPS)
    (F1, L1), (F2, L2) = small.incidence.shape, big.incidence.shape
    F, L = max(F1, F2), max(L1, L2)
    assert share == pytest.approx(1 - (F1 * L1 + F2 * L2) / (2 * F * L))
    assert [len(r) for r in rates] == [F1, F2]
    assert converged_phase_rates([small], steps=STEPS)[1] == 0.0


@pytest.mark.parametrize("moe", [False, True], ids=["gpt128", "moe128"])
def test_run_batch_matches_each_scenario_solved_alone(moe):
    """``run_batch`` pads a sweep's flow scenarios into one program at
    dt 1e-5; each scenario's rates are its own solve's."""
    scn = training_scenario(n_gpus=128, moe=moe)
    busy = [ph for ph in scn.build_phases() if ph.flows]
    scns = [Scenario(f"phase{i}", scn.topology, flows=[
        FlowSpec(f.fid, f.src, f.dst, f.size, 0.0, "dctcp") for f in ph.flows])
        for i, ph in enumerate(busy[:2] + busy[-2:])]
    topo = scn.build_topology()
    for s, res in zip(scns, get_engine("fluid").run_batch(scns, steps=STEPS)):
        fs = FluidScenario.from_flows(
            topo, [(f.fid, f.src, f.dst, f.size) for f in s.flows])
        alone = fluid_converged_rates(fs, dt=1e-5, steps=STEPS)["rates"]
        np.testing.assert_array_equal(
            np.array(list(res.extras["rates"].values()), np.float32), alone)
