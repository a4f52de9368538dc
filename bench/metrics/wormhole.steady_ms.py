"""wormhole.steady_ms: host milliseconds per traced request in the
wormhole kernel's monitor samples (``repro.kernel.sample``: steady
detection, and the parks and reshapes it sets off)."""
from bench.harness import spans


def read(rec):
    s = spans.per_request(rec, "total_s", "repro.kernel.sample")
    return None if s is None else 1e3 * s
