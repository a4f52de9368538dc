"""wormhole.memo_ms: host milliseconds per traced request in the wormhole
kernel's memo queries (``repro.wormhole.memo``: the FCG build and the
SimDB lookup of each partition formed)."""
from bench.harness import spans


def read(rec):
    s = spans.per_request(rec, "total_s", "repro.wormhole.memo")
    return None if s is None else 1e3 * s
