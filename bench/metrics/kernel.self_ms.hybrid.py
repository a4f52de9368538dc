"""kernel.self_ms.hybrid: host milliseconds per traced request inside the
hybrid kernel's hooks: the self times of the ``repro.kernel.*`` spans
(granularity control, demotions, promotions) and of ``repro.hybrid.*``
(the max-min solves)."""
from bench.harness import spans


def read(rec):
    s = spans.per_request(rec, "self_s", "repro.kernel.", "repro.hybrid.")
    return None if s is None else 1e3 * s
