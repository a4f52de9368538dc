"""api.prep_ms.hybrid: ``api.prep_ms`` of the hybrid cell (it moves
``iter_s.hybrid``): host milliseconds a traced request spends in
``repro.api.run`` outside the engine's timed loop, from the program's
spans (self of ``repro.run``, totals of ``repro.api.build`` and
``repro.api.collect``)."""
from bench.harness import spans


def read(rec):
    own = spans.per_request(rec, "self_s", "repro.run")
    if own is None:
        return None
    return 1e3 * (own + spans.per_request(rec, "total_s", "repro.api."))
