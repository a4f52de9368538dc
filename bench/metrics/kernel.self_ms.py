"""kernel.self_ms: host milliseconds per traced request inside the
wormhole kernel's hooks: the self times of the ``repro.kernel.*`` spans
(admit, finish, sample, event, chaos: partitioning, steady detection,
unparks) and of ``repro.wormhole.*`` (memo query and replay)."""
from bench.harness import spans


def read(rec):
    s = spans.per_request(rec, "self_s", "repro.kernel.", "repro.wormhole.")
    return None if s is None else 1e3 * s
