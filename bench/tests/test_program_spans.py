"""The program's spans in a traced run (``bench/harness/spans.py``) and the
per-layer metrics that read them."""
import pytest
import test_metrics
from conftest import ROOT

from bench.harness import spans, spec, trace

HOST = "/host:CPU"


def ev(name, a, b, line="python", plane=HOST):
    return (plane, line, name, float(a), float(b))


# a 1000 ns window: one request whole, one cut by the window's end, one
# after it; a kernel hook on a second thread; a device event
EVENTS = [
    ev("bench.window", 0, 1000),
    ev("bench.run", 10, 490),
    ev("repro.run", 20, 480),
    ev("repro.api.build", 20, 50),
    ev("repro.engine.loop", 50, 470),
    ev("repro.kernel.sample", 100, 200),
    ev("repro.wormhole.memo", 120, 150),
    ev("repro.kernel.event", 300, 320),
    ev("repro.api.collect", 470, 480),
    ev("repro.run", 900, 1100),
    ev("repro.engine.loop", 950, 1050),
    ev("repro.run", 1200, 1300),
    ev("repro.kernel.event", 100, 300, line="worker"),
    ev("repro.fluid.launch", 0, 10, plane="/device:TPU:0"),
]


def test_reduce_counts_clips_and_takes_self_time():
    r = {k: (v["n"], round(v["total_s"] * 1e9, 6),
             round(v["self_s"] * 1e9, 6))
         for k, v in spans.reduce(EVENTS).items()}
    assert r == {
        # 20-480 whole, under its three children; 900-1000 of the second,
        # less the loop's 950-1000; the third is outside the window
        "repro.run": (2, 460 + 100, 0 + 50),
        "repro.api.build": (1, 30, 30),
        "repro.engine.loop": (2, 420 + 50, 420 - 100 - 20 + 50),
        "repro.kernel.sample": (1, 100, 70),
        "repro.wormhole.memo": (1, 30, 30),
        # the worker thread's hook is no child of the loop
        "repro.kernel.event": (2, 20 + 200, 20 + 200),
        "repro.api.collect": (1, 10, 10),
    }


def test_reduce_wants_one_window():
    with pytest.raises(RuntimeError):
        spans.reduce([e for e in EVENTS if e[2] != "bench.window"])


def test_spans_of_a_recorded_trace(tmp_path):
    """The program's spans as the profiler writes them (here on the CPU)."""
    import jax
    from jaxlib._profiler import TraceMe
    with trace.capture(tmp_path):
        with jax.profiler.TraceAnnotation("bench.window"):
            with TraceMe("repro.run"):
                for _ in range(3):
                    with TraceMe("repro.engine.loop"):
                        pass
    r = spans.reduce(spans.load_events(tmp_path))
    assert set(r) == {"repro.run", "repro.engine.loop"}
    assert r["repro.engine.loop"]["n"] == 3
    run = r["repro.run"]
    assert 0 < run["self_s"] < run["total_s"]
    assert run["total_s"] == pytest.approx(
        run["self_s"] + r["repro.engine.loop"]["total_s"])


S = 1e-3
REC = {
    "traced_requests": 2,
    "requests": [{"events": 1000}, {"events": 3000}, {"events": 99999}],
    "trace": {"window_s": 1.0, "busy_s": 0.0, "programs": {}, "spans": {
        "repro.run": {"n": 2, "total_s": 1.0, "self_s": 20 * S},
        "repro.api.build": {"n": 2, "total_s": 30 * S, "self_s": 30 * S},
        "repro.api.collect": {"n": 2, "total_s": 10 * S, "self_s": 10 * S},
        "repro.engine.loop": {"n": 2, "total_s": 0.94, "self_s": 0.6},
        "repro.kernel.admit": {"n": 4, "total_s": 50 * S, "self_s": 10 * S},
        "repro.kernel.sample": {"n": 50, "total_s": 0.2, "self_s": 0.12},
        "repro.kernel.event": {"n": 10, "total_s": 90 * S, "self_s": 50 * S},
        "repro.wormhole.memo": {"n": 8, "total_s": 80 * S, "self_s": 80 * S},
        "repro.wormhole.replay": {"n": 6, "total_s": 20 * S,
                                  "self_s": 20 * S},
        "repro.hybrid.solve": {"n": 20, "total_s": 40 * S, "self_s": 40 * S},
    }},
}

EXPECTED = {
    "api.prep_ms": (20 + 30 + 10) / 2,
    "loop.self_us_per_event": 1e6 * 0.3 / 2000,   # events of the traced two
    "kernel.self_ms": (10 + 120 + 50 + 80 + 20) / 2,
    "kernel.self_ms.hybrid": (10 + 120 + 50 + 40) / 2,
    "wormhole.memo_ms": 40.0,
    "wormhole.steady_ms": 100.0,
    "wormhole.memo_hit_rate": 0.75,
    "hybrid.solve_ms": 20.0,
    "hybrid.solves": 10.0,
}
EXPECTED.update({f"{n}.hybrid": EXPECTED[n] for n in (
    "api.prep_ms", "loop.self_us_per_event")})
# these metrics join the register that test_every_metric_file_is_tested
# compares with BENCHMARK.json
test_metrics.EXPECTED.update(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_metric_arithmetic(name):
    assert spec.load_reader(ROOT, name)(REC) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_metric_is_silent_without_program_spans(name):
    """No trace (an untraced or rehearsed run), or a program that records
    no spans: the metric reads nothing."""
    read = spec.load_reader(ROOT, name)
    assert read({**REC, "trace": None}) is None
    assert read({**REC, "trace": {**REC["trace"], "spans": {}}}) is None
