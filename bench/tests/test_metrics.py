"""Each metric file's arithmetic, on a made-up run."""
import pytest
from conftest import ROOT

from bench.harness import spec

REC = {
    "setup_s": 12.5, "window_s": 10.0, "traced_requests": 2,
    "requests": [
        {"host_s": 2.0, "wall_s": 1.5, "events": 100,
         "packet_lane_events": 30, "flow_lane_events": 5},
        {"host_s": 3.0, "wall_s": 2.5, "events": 300,
         "packet_lane_events": 50, "flow_lane_events": 7},
        {"host_s": 5.0, "wall_s": 4.0, "events": 200,
         "packet_lane_events": 40, "flow_lane_events": 6}],
    "reference": {"events": 1000},
    "compared": {"fct_err_mean": 0.0025},
    "trace": {"window_s": 4.0, "busy_s": 1.0,
              "programs": {"jit_fluid_run": 0.03, "jit_other": 0.5}},
}

EXPECTED = {
    "iter_s": 10.0 / 3,
    "iter_s_p90": 4.6,                     # 3 + 0.8 * (5 - 3)
    "setup_s": 12.5,
    "api.build_s": (0.5 + 0.5 + 1.0) / 3,
    "loop.us_per_event": 1e6 * 8.0 / 600,
    "wormhole.event_ratio": 0.2,
    "wormhole.fct_err_pct": 0.25,
    "hybrid.packet_lane_ratio": 0.04,
    "hybrid.fct_err_pct": 0.25,
    "fluid.device_ms": 15.0,
    "device.idle_share": 0.75,
}
# the hybrid cell's own copies of four metrics read the same arithmetic
EXPECTED.update({f"{n}.hybrid": EXPECTED[n] for n in (
    "iter_s", "api.build_s", "loop.us_per_event", "device.idle_share")})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_arithmetic(name):
    assert spec.load_reader(ROOT, name)(REC) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ["fluid.device_ms", "device.idle_share",
                                  "device.idle_share.hybrid"])
def test_device_metric_is_silent_without_a_trace(name):
    assert spec.load_reader(ROOT, name)({**REC, "trace": None}) is None


def test_fluid_time_is_silent_where_no_fluid_program_ran():
    rec = {**REC, "trace": {**REC["trace"], "programs": {"jit_other": 1.0}}}
    assert spec.load_reader(ROOT, "fluid.device_ms")(rec) is None


def test_counts_are_silent_without_a_packet_reference():
    rec = {**REC, "reference": {"events": None}}
    for name in ("wormhole.event_ratio", "hybrid.packet_lane_ratio"):
        assert spec.load_reader(ROOT, name)(rec) is None


def test_every_metric_file_is_tested(bench_json):
    names = {m["name"] for m in bench_json["end_to_end"]
             + bench_json["per_layer"]}
    assert names == set(EXPECTED)
