"""Spans on the JAX profiler's host trace (``repro.spans``): free while no
trace runs, recorded at the simulator's layer boundaries while one does,
and without any effect on what the simulator computes."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.api import run
from repro.spans import NO_SPAN, span
from test_api import wave_scenario
from test_chaos import DEGRADE

ROOT = pathlib.Path(__file__).resolve().parents[1]

# one port degraded mid-run, so the chaos hook fires too (the fluid
# backend refuses link events); the quarter-size waves make wormhole
# replay its first wave's transient and hybrid demote through solves
BACKENDS = {
    "packet": ({}, [DEGRADE]),
    "wormhole": ({}, [DEGRADE]),
    "hybrid": ({"fidelity": "auto"}, [DEGRADE]),
    "fluid": ({}, []),
}
API = {"repro.api.build", "repro.engine.loop", "repro.api.collect"}
KERNEL = {f"repro.kernel.{k}" for k in ("admit", "finish", "sample",
                                        "event", "chaos")}
EXPECTED = {
    "packet": API,
    "wormhole": API | KERNEL | {"repro.wormhole.memo",
                                "repro.wormhole.replay"},
    "hybrid": API | KERNEL | {"repro.hybrid.solve"},
    "fluid": API | {f"repro.fluid.{k}" for k in ("build", "launch", "fetch",
                                                 "converge")},
}


def scenario(backend):
    opts, chaos = BACKENDS[backend]
    scn = wave_scenario(size_scale=0.25)
    if chaos:
        scn = scn.variant(chaos=chaos)
    return scn, opts


def traced_run(backend, log_dir):
    """Run ``backend`` under a profiler trace; the result and the
    ``(name, start, end, parent name)`` of every ``repro.*`` span."""
    scn, opts = scenario(backend)
    run(scn, backend=backend, **opts)          # compile outside the trace
    popts = jax.profiler.ProfileOptions()
    popts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=popts)
    try:
        res = run(scn, backend=backend, **opts)
    finally:
        jax.profiler.stop_trace()
    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name.startswith("repro.")),
                         key=lambda e: (e[0], -e[1]))
            stack = []
            for a, b, name in evs:
                while stack and stack[-1][1] <= a:
                    stack.pop()
                spans.append((name, a, b, stack[-1][2] if stack else None))
                stack.append((a, b, name))
    return res, spans


def outcome(res):
    return res.fcts, res.events_processed, res.kernel_report


def test_import_loads_no_jax():
    code = ("import sys, repro.spans; "
            "sys.exit(any(m.split('.')[0] in ('jax', 'jaxlib') "
            "for m in sys.modules))")
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    assert subprocess.run([sys.executable, "-c", code], env=env).returncode == 0


def test_span_is_the_shared_no_op_without_a_trace():
    assert "jaxlib._profiler" in sys.modules      # jax is loaded here
    assert span("repro.run") is NO_SPAN
    with span("repro.run"):
        pass


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_spans_recorded_with_their_nesting(backend, tmp_path):
    res, spans = traced_run(backend, tmp_path)
    names = {s[0] for s in spans}
    assert names == EXPECTED[backend] | {"repro.run"}
    count = {n: sum(1 for s in spans if s[0] == n) for n in names}
    assert count["repro.run"] == 1
    parents = {n: {s[3] for s in spans if s[0] == n} for n in names}
    assert parents["repro.run"] == {None}
    assert all(parents[n] == {"repro.run"} for n in API)
    for n in names - API - {"repro.run"}:
        # kernel hooks run inside the loop, and inside one another (a flow
        # that finishes during a replay); memo, replay and solves inside a
        # hook; the fluid host path inside the loop
        outer = ({"repro.engine.loop"} if n.startswith(("repro.kernel.",
                                                        "repro.fluid."))
                 else set())
        assert parents[n] <= outer | KERNEL, n
    (loop,) = [s for s in spans if s[0] == "repro.engine.loop"]
    assert (loop[2] - loop[1]) * 1e-9 == pytest.approx(res.wall_time,
                                                       rel=0.02, abs=1e-3)
    if backend in ("wormhole", "hybrid"):
        assert count["repro.kernel.chaos"] == 1
    if backend == "wormhole":
        rep = res.kernel_report
        assert count["repro.wormhole.replay"] == rep["replays"] > 0
        assert count["repro.wormhole.memo"] == rep["run_db_lookups"]
    if backend == "hybrid":
        assert count["repro.hybrid.solve"] == res.kernel_report["solves"] > 0


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_a_trace_changes_no_result(backend, tmp_path):
    scn, opts = scenario(backend)
    plain = run(scn, backend=backend, **opts)
    traced, _ = traced_run(backend, tmp_path)
    assert outcome(traced) == outcome(plain)
