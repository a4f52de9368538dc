"""Campaign API: durable, resumable, observable experiment sessions.

Acceptance (ISSUE 5): a campaign killed after K of N sweep runs re-opens
and completes only the N−K remaining scenarios, with cache-hit events
observed for the K completed ones; a resubmitted identical scenario
returns the stored RunResult (equal through the JSON round-trip) without
invoking the engine.

This file doubles as the crash harness for the kill-mid-sweep test: run
directly (``python tests/test_campaign.py CAMPAIGN_DIR K``) it starts the
sweep and hard-exits (``os._exit`` — no atexit, no close, no flush)
after K committed runs.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import (Campaign, Engine, FlowSpec, RunResult, Scenario,
                       SimDB, TopologySpec, register_engine, run_key)
from repro.api.engines import _REGISTRY
from repro.api.store import RunStore, scenario_fingerprint

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def flows_scenario(scale: float = 1.0, name: str = "camp-waves") -> Scenario:
    flows = []
    fid = 0
    for wave in (0.0, 0.02):
        for i in range(4):
            flows.append(FlowSpec(fid, i, 12 + (i % 2), size=4e6 * scale,
                                  start=wave, cca="dctcp"))
            fid += 1
    return Scenario(name, TopologySpec("clos", {"n_hosts": 16, "leaf_down": 4,
                                                "n_spines": 2}), flows=flows)


def sweep_scenarios(n: int = 6) -> list[Scenario]:
    """The kill-mid-sweep scenario list — must build identically in the
    crash subprocess and the resuming parent (content-addressed keys)."""
    return [flows_scenario(1.0 + 0.1 * i, name=f"sw{i}") for i in range(n)]


class CountingEngine(Engine):
    """Registry-pluggable engine that counts invocations — how the tests
    prove a cache hit never reached an engine."""
    calls = 0

    def run(self, scenario, **opts):
        type(self).calls += 1
        return RunResult(backend=self.name, scenario=scenario.name,
                         fcts={f.fid: 1.0 + f.size * 1e-9
                               for f in scenario.flows},
                         flow_bytes={f.fid: f.size for f in scenario.flows},
                         tags={f.fid: f.tag for f in scenario.flows},
                         iteration_time=1.0, events_processed=7,
                         wall_time=0.0, extras={"probe": [1, 2]})


@pytest.fixture
def counting_engine():
    register_engine("counting")(CountingEngine)
    CountingEngine.calls = 0
    yield CountingEngine
    _REGISTRY.pop("counting", None)


# --------------------------------------------------------------------- #
# RunStore + keys
# --------------------------------------------------------------------- #
def test_run_key_is_content_addressed():
    a, b = flows_scenario(), flows_scenario()
    assert scenario_fingerprint(a) == scenario_fingerprint(b)
    assert run_key(a, "packet", {}) == run_key(b, "packet", {})
    assert run_key(a, "packet", {}) != run_key(a, "wormhole", {})
    assert run_key(a, "packet", {}) != run_key(a, "packet", {"until": 1.0})
    assert run_key(flows_scenario(1.1), "packet", {}) != \
        run_key(a, "packet", {})
    # opt *order* must not matter, only content
    assert run_key(a, "hybrid", {"fidelity": "auto", "demote_after": 4}) == \
        run_key(a, "hybrid", {"demote_after": 4, "fidelity": "auto"})


def test_run_key_uncacheable_and_array_opts():
    """Opts with no canonical JSON form never dedup (a repr could truncate
    or embed a reused memory address); ndarray opts key by content."""
    scn = flows_scenario()
    db = SimDB()
    assert run_key(scn, "wormhole", {"db": db}) != \
        run_key(scn, "wormhole", {"db": db})
    big = np.arange(2000)
    near = big.copy()
    near[1000] = -1                       # differs only in the repr-elided middle
    assert run_key(scn, "packet", {"x": big}) == \
        run_key(scn, "packet", {"x": big.copy()})
    assert run_key(scn, "packet", {"x": big}) != \
        run_key(scn, "packet", {"x": near})


def test_run_store_disk_roundtrip(tmp_path, counting_engine):
    store = RunStore(tmp_path / "runs")
    scn = flows_scenario()
    result = CountingEngine().run(scn)
    key = run_key(scn, "counting", {})
    assert store.get(key) is None and store.misses == 1
    store.put(key, scn, "counting", {}, result)
    assert key in store and len(store) == 1 and store.keys() == [key]
    rec = store.get(key)
    assert store.hits == 1
    assert rec["backend"] == "counting"
    assert rec["scenario"] == scn.to_dict()
    assert RunResult.from_dict(rec["result"]).to_dict() == result.to_dict()
    # no torn/tmp files left next to the committed record
    assert [p.name for p in (tmp_path / "runs").iterdir()] == [f"{key}.json"]
    # a fresh store over the same dir sees the same record
    again = RunStore(tmp_path / "runs")
    assert again.get(key) == rec
    assert again.delete(key) and not again.delete(key)
    assert len(again) == 0


def test_run_store_rejects_foreign_record_version(tmp_path, counting_engine):
    store = RunStore(tmp_path / "runs")
    scn = flows_scenario()
    key = run_key(scn, "counting", {})
    store.put(key, scn, "counting", {}, CountingEngine().run(scn))
    rec = json.loads((tmp_path / "runs" / f"{key}.json").read_text())
    rec["record_version"] = 99
    (tmp_path / "runs" / f"{key}.json").write_text(json.dumps(rec))
    with pytest.raises(ValueError, match="record_version"):
        store.get(key)


def test_records_skips_corrupt_files_with_warning(tmp_path, counting_engine):
    """A truncated/garbled record must not poison iteration (dataset
    extraction reads the whole store): it is skipped with a warning,
    surfaces in corrupt_keys(), reads as a miss, and a resubmission of the
    same triple heals it."""
    store = RunStore(tmp_path / "runs")
    scns = [flows_scenario(1.0 + 0.1 * i, name=f"c{i}") for i in range(3)]
    keys = [run_key(s, "counting", {}) for s in scns]
    for scn, key in zip(scns, keys):
        store.put(key, scn, "counting", {}, CountingEngine().run(scn))
    bad = tmp_path / "runs" / f"{keys[1]}.json"
    bad.write_text(bad.read_text()[:40])             # torn copy
    with pytest.warns(RuntimeWarning, match="corrupt run record"):
        recs = list(store.records())
    assert [r["key"] for r in recs] == sorted([keys[0], keys[2]])
    assert store.corrupt_keys() == [keys[1]]
    assert store.get(keys[1]) is None and keys[1] not in store
    # rewriting the record heals it without a stale corrupt marker
    store.put(keys[1], scns[1], "counting", {}, CountingEngine().run(scns[1]))
    assert store.corrupt_keys() == [] and store.get(keys[1]) is not None
    assert len(list(store.records())) == 3


def test_campaign_resubmit_heals_corrupt_record(tmp_path, counting_engine):
    with Campaign.open(tmp_path / "camp") as camp:
        h = camp.submit(flows_scenario(), backend="counting")
        rec_file = tmp_path / "camp" / "runs" / f"{h.key}.json"
        rec_file.write_text("{not json")
        with pytest.warns(RuntimeWarning, match="corrupt"):
            h2 = camp.submit(flows_scenario(), backend="counting")
    assert not h2.cached and CountingEngine.calls == 2
    assert json.loads(rec_file.read_text())["key"] == h.key


def test_run_store_in_memory_matches_disk_shape(tmp_path, counting_engine):
    scn = flows_scenario()
    result = CountingEngine().run(scn)
    key = run_key(scn, "counting", {})
    mem, disk = RunStore(None), RunStore(tmp_path / "runs")
    mem.put(key, scn, "counting", {}, result)
    disk.put(key, scn, "counting", {}, result)
    assert mem.get(key) == disk.get(key)   # same canonical JSON either way


# --------------------------------------------------------------------- #
# submit: dedup without invoking the engine
# --------------------------------------------------------------------- #
def test_submit_dedup_skips_engine(tmp_path, counting_engine):
    camp = Campaign.open(tmp_path / "camp", name="dedup")
    events = []
    camp.subscribe(lambda e: events.append(e.kind))
    h1 = camp.submit(flows_scenario(), backend="counting")
    h2 = camp.submit(flows_scenario(), backend="counting")
    assert CountingEngine.calls == 1
    assert not h1.cached and h2.cached and h1.key == h2.key
    assert events == ["started", "finished", "cache_hit"]
    # the cached result IS the stored one, equal through the JSON round-trip
    assert h2.result.to_dict() == h1.result.to_dict()
    assert h2.result.to_dict() == \
        json.loads(json.dumps(h1.result.to_dict()))
    assert h2.result.fcts == h1.result.fcts          # int keys restored
    # different opts are a different experiment
    camp.submit(flows_scenario(), backend="counting", until=2.0)
    assert CountingEngine.calls == 2
    camp.close()


def test_submit_dedup_survives_reopen(tmp_path, counting_engine):
    camp = Campaign.open(tmp_path / "camp")
    first = camp.submit(flows_scenario(), backend="counting").result
    camp.close()
    camp2 = Campaign.open(tmp_path / "camp")
    h = camp2.submit(flows_scenario(), backend="counting")
    assert h.cached and CountingEngine.calls == 1
    assert h.result.to_dict() == first.to_dict()
    camp2.close()


def test_sweep_dedups_identical_scenarios_within_one_call(counting_engine):
    camp = Campaign.in_memory()
    kinds = []
    camp.subscribe(lambda e: kinds.append(e.kind))
    results = camp.sweep([flows_scenario(), flows_scenario(),
                          flows_scenario(1.5, name="other")],
                         backend="counting")
    assert CountingEngine.calls == 2
    assert kinds.count("cache_hit") == 1
    assert results[0].fcts == results[1].fcts
    assert results[2].scenario == "other"


# --------------------------------------------------------------------- #
# durable campaign invariants
# --------------------------------------------------------------------- #
def test_durable_campaign_owns_its_simdb(tmp_path):
    with pytest.raises(ValueError, match="owns its SimDB"):
        Campaign(tmp_path / "camp", db=SimDB())
    camp = Campaign.open(tmp_path / "camp")
    with pytest.raises(ValueError, match="owns its SimDB"):
        camp.submit(flows_scenario(), backend="wormhole", db=SimDB())
    with pytest.raises(ValueError, match="owns its SimDB"):
        camp.sweep([flows_scenario()], backend="wormhole", db=SimDB())
    camp.close()


def test_manifest_roundtrip_and_version_check(tmp_path):
    camp = Campaign.open(tmp_path / "camp", name="paper-sweeps")
    camp.close()
    assert Campaign.open(tmp_path / "camp").name == "paper-sweeps"
    manifest = tmp_path / "camp" / "campaign.json"
    manifest.write_text(json.dumps({"manifest_version": 99}))
    with pytest.raises(ValueError, match="manifest_version"):
        Campaign.open(tmp_path / "camp")


def test_campaign_simdb_warms_across_sessions(tmp_path):
    """The campaign's own SimDB (no caller-managed file plumbing)
    fast-forwards a new variant submitted in a later session."""
    camp = Campaign.open(tmp_path / "camp")
    cold = camp.submit(flows_scenario(1.0, name="v1"),
                       backend="wormhole").result
    camp.close()
    assert (tmp_path / "camp" / "simdb.json").exists()
    camp2 = Campaign.open(tmp_path / "camp")
    warm = camp2.submit(flows_scenario(1.1, name="v2"),
                        backend="wormhole").result
    assert warm.kernel_report["run_db_hits"] > 0
    assert warm.events_processed < cold.events_processed
    camp2.close()


def test_results_and_records_filters(counting_engine):
    camp = Campaign.in_memory()
    camp.submit(flows_scenario(name="a"), backend="counting")
    camp.submit(flows_scenario(name="b"), backend="counting")
    camp.submit(flows_scenario(name="a"), backend="analytic")
    assert len(camp.results()) == 3 and len(camp) == 3
    assert len(camp.results(backend="counting")) == 2
    assert {r["scenario"]["name"]
            for r in camp.records(backend="analytic")} == {"a"}
    assert len(camp.results(scenario="a")) == 2
    assert all(isinstance(r, RunResult) for r in camp.results())


def test_campaign_compare_hits_store_on_repeat(counting_engine):
    camp = Campaign.in_memory()
    cmp1 = camp.compare(flows_scenario(), backends=("counting", "analytic"))
    calls = CountingEngine.calls
    cmp2 = camp.compare(flows_scenario(), backends=("counting", "analytic"))
    assert CountingEngine.calls == calls           # all served from store
    assert cmp2["counting"].to_dict() == cmp1["counting"].to_dict()
    with pytest.raises(ValueError, match="baseline"):
        camp.compare(flows_scenario(), backends=("counting",),
                     baseline="analytic")


def test_observer_unsubscribe(counting_engine):
    camp = Campaign.in_memory()
    seen = []
    cb = camp.subscribe(lambda e: seen.append(e.kind))
    camp.submit(flows_scenario(), backend="counting")
    camp.unsubscribe(cb)
    camp.submit(flows_scenario(1.2, name="other"), backend="counting")
    assert seen == ["started", "finished"]


# --------------------------------------------------------------------- #
# the acceptance test: kill mid-sweep, re-open, resume
# --------------------------------------------------------------------- #
def _crash_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_kill_mid_sweep_resume(tmp_path):
    """A campaign hard-killed after K of N sweep runs re-opens and
    completes only the N−K remainder; the K completed runs surface as
    cache-hit events; a resubmitted identical scenario returns the stored
    result without simulating."""
    cdir = str(tmp_path / "camp")
    n, k = 6, 3
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), cdir, str(k)],
        env=_crash_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 17, (proc.stdout, proc.stderr)

    camp = Campaign.open(cdir)
    assert len(camp.store) == k        # exactly K committed, none torn
    stored_before = {key: camp.store._peek(key) for key in camp.store.keys()}

    events = []
    camp.subscribe(events.append)
    results = camp.sweep(sweep_scenarios(n), backend="analytic")
    kinds = [e.kind for e in events]
    assert kinds.count("cache_hit") == k
    assert kinds.count("started") == kinds.count("finished") == n - k
    assert all(r is not None for r in results)
    # the K cached results came through the JSON round-trip unchanged
    hit_keys = {e.key for e in events if e.kind == "cache_hit"}
    assert hit_keys == set(stored_before)
    for e in events:
        if e.kind == "cache_hit":
            assert e.result.to_dict() == stored_before[e.key]["result"]

    # resubmitting one completed scenario is a pure store read
    h = camp.submit(sweep_scenarios(n)[0], backend="analytic")
    assert h.cached
    assert h.result.to_dict() == results[0].to_dict()
    camp.close()

    # a fully resumed campaign has nothing left to run
    camp2 = Campaign.open(cdir)
    kinds2 = []
    camp2.subscribe(lambda e: kinds2.append(e.kind))
    camp2.sweep(sweep_scenarios(n), backend="analytic")
    assert kinds2.count("cache_hit") == n and "started" not in kinds2
    camp2.close()


@pytest.mark.slow
def test_parallel_sweep_resume_with_workers(tmp_path):
    """workers=2 sweeps commit incrementally too: a half sweep's results
    are all cache hits for the full parallel sweep that follows."""
    cdir = tmp_path / "camp"
    scns = [flows_scenario(1.0 + 0.1 * i, name=f"p{i}") for i in range(4)]
    camp = Campaign.open(cdir)
    camp.sweep(scns[:2], backend="wormhole", workers=2)
    camp.close()
    camp2 = Campaign.open(cdir)
    kinds = []
    camp2.subscribe(lambda e: kinds.append(e.kind))
    results = camp2.sweep(scns, backend="wormhole", workers=2)
    assert kinds.count("cache_hit") == 2
    assert kinds.count("finished") == 2
    assert [r.scenario for r in results] == [s.name for s in scns]
    # the campaign DB accumulated entries from both sessions' workers
    assert len(camp2.db) > 0
    camp2.close()


@pytest.mark.parametrize("backend", ["fluid", "learned"])
def test_device_backend_sweep_refuses_workers(backend):
    """A backend that runs on the accelerator is never fanned out over
    spawn workers: the parent may hold the device the children need."""
    with Campaign.in_memory(name="one-device") as camp:
        events = []
        camp.subscribe(events.append)
        with pytest.raises(ValueError, match="one process"):
            camp.sweep([flows_scenario()], backend=backend, workers=2)
        assert events == [] and len(camp) == 0


# --------------------------------------------------------------------- #
# CLI (python -m repro) over the same API
# --------------------------------------------------------------------- #
def _cli(*args, cwd=None):
    return subprocess.run([sys.executable, "-m", "repro", *args],
                          env=_crash_env(), capture_output=True, text=True,
                          cwd=cwd, timeout=300)


def test_cli_run_ls_show_rm_roundtrip(tmp_path):
    scn_file = tmp_path / "demo.json"
    scn_file.write_text(flows_scenario(name="cli-demo").to_json())
    cdir = str(tmp_path / "camp")

    out = _cli("run", str(scn_file), "--backend", "analytic", "-c", cdir)
    assert out.returncode == 0, out.stderr
    assert "cli-demo" in out.stdout and "running" in out.stdout

    # second invocation of the same triple is a cache hit
    out = _cli("run", str(scn_file), "--backend", "analytic", "-c", cdir)
    assert out.returncode == 0 and "cache hit" in out.stdout

    out = _cli("sweep", str(scn_file), "--backend", "analytic", "-c", cdir)
    assert out.returncode == 0, out.stderr
    assert "1 from the store, 0 simulated" in out.stdout

    out = _cli("ls", "-c", cdir)
    assert out.returncode == 0 and "analytic" in out.stdout
    key = out.stdout.split()[0]

    out = _cli("show", key, "-c", cdir)
    assert out.returncode == 0
    rec = json.loads(out.stdout)
    assert rec["backend"] == "analytic"
    assert rec["scenario"]["name"] == "cli-demo"

    assert _cli("show", "deadbeef", "-c", cdir).returncode == 1
    # rm refuses an ambiguous prefix (two stored runs share the empty one)
    _cli("run", str(scn_file), "--backend", "packet", "-c", cdir)
    bad = _cli("rm", "", "-c", cdir)
    assert bad.returncode == 1 and "ambiguous" in bad.stderr
    out = _cli("rm", key, "-c", cdir)
    assert out.returncode == 0 and "removed 1" in out.stdout
    assert "1 stored runs" in _cli("ls", "-c", cdir).stdout


def test_cli_engine_opts_reach_the_engine(tmp_path):
    scn_file = tmp_path / "demo.json"
    scn_file.write_text(flows_scenario(name="cli-opts").to_json())
    out = _cli("run", str(scn_file), "--backend", "hybrid",
               "--opt", "fidelity=flow")
    assert out.returncode == 0, out.stderr
    # a bad opt value must fail loudly, not run with defaults
    bad = _cli("run", str(scn_file), "--backend", "hybrid",
               "--opt", "fidelity=warp")
    assert bad.returncode != 0


if __name__ == "__main__":
    # crash harness: sweep, then hard-exit (no atexit/close) after K commits
    cdir, k = sys.argv[1], int(sys.argv[2])
    camp = Campaign.open(cdir)
    done = [0]

    def _chaos(event):
        if event.kind == "finished":
            done[0] += 1
            if done[0] >= k:
                os._exit(17)

    camp.subscribe(_chaos)
    camp.sweep(sweep_scenarios(), backend="analytic")
    os._exit(0)                        # not reached when k < len(sweep)
