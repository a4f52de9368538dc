"""hybrid.solve_ms: host milliseconds per traced request in the hybrid
kernel's max-min solves (``repro.hybrid.solve``)."""
from bench.harness import spans


def read(rec):
    s = spans.per_request(rec, "total_s", "repro.hybrid.solve")
    return None if s is None else 1e3 * s
