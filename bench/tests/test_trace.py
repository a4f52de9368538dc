"""The reduction from a profiler trace to busy, idle and program time."""
import gzip
import json

import pytest
from conftest import DATA

from bench.harness import trace

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, a, b):
    return (plane, line, name, float(a), float(b))


# a 100 ns window; two requests, each with a run() span; device ops at
# 10-30 (twice overlapping) and 60-70, programs around them
SMALL = [
    ev(HOST, "python", "bench.window", 0, 100),
    ev(HOST, "python", "bench.request", 0, 50),
    ev(HOST, "python", "bench.run", 5, 45),
    ev(HOST, "python", "bench.request", 50, 100),
    ev(HOST, "python", "bench.run", 55, 95),
    ev(HOST, "python", "something.else", 0, 100),
    ev(DEV, trace.OPS_LINE, "fusion.1", 10, 25),
    ev(DEV, trace.OPS_LINE, "fusion.2 = f32[64] fusion(f32[64] %a)", 20, 30),
    ev(DEV, trace.OPS_LINE, "fusion.1", 60, 70),
    ev(DEV, trace.OPS_LINE, "outside", 120, 150),
    ev(DEV, trace.MODULES_LINE, "jit_fluid_run(17)", 10, 30),
    ev(DEV, trace.MODULES_LINE, "jit_fluid_run(23)", 60, 70),
]


def test_union_and_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace._gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]


def test_reduce_small_trace():
    r = trace.reduce(SMALL)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)       # 10-30 and 60-70
    lone = [e for e in SMALL if e[1] != trace.OPS_LINE]
    assert trace.reduce(lone)["busy_s"] == pytest.approx(30e-9)
    assert r["devices"] == 1
    assert r["programs"] == {"jit_fluid_run": pytest.approx(30e-9)}
    assert dict(r["device_ops"]) == {"fusion.1": pytest.approx(25e-9),
                                     "fusion.2": pytest.approx(10e-9)}
    # idle 0-10, 30-60, 70-100: 0-5, 45-55 and 95-100 under a request
    # only, 5-10, 30-45, 55-60 and 70-95 under run()
    idle = dict(r["idle_gaps"])
    assert idle == {"bench.request": pytest.approx(20e-9),
                    "bench.run": pytest.approx(50e-9)}
    assert sum(idle.values()) == pytest.approx(70e-9)


def test_busy_is_averaged_over_devices():
    two = SMALL + [ev("/device:TPU:1", trace.OPS_LINE, "fusion.9", 0, 100)]
    assert trace.reduce(two)["busy_s"] == pytest.approx(65e-9)


def test_one_window_span_is_required():
    with pytest.raises(RuntimeError):
        trace.reduce([e for e in SMALL if e[2] != "bench.window"])


def test_recorded_chip_trace():
    """Two milliseconds of a fluid cell's traced window as a TPU v5e
    recorded it (cut by ``record_trace.py``): the device's ``XLA Ops`` and
    ``XLA Modules`` events and the harness's host spans."""
    with gzip.open(DATA / "trace_fluid_v5e.json.gz", "rt") as f:
        events = [tuple(e) for e in json.load(f)]
    r = trace.reduce(events)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    fluid = sum(v for k, v in r["programs"].items()
                if k.startswith("jit_fluid_run"))
    assert 0 < fluid <= r["busy_s"] + 1e-9
    assert r["device_ops"] and all(" = " not in k for k, _ in r["device_ops"])
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    expected = json.loads((DATA / "trace_fluid_v5e.expected.json")
                          .read_text())
    for k, v in expected.items():
        assert r[k] == pytest.approx(v), k
