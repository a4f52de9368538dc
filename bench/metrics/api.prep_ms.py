"""api.prep_ms: host milliseconds a traced request spends in
``repro.api.run`` outside the engine's timed loop, from the program's
spans: the self time of ``repro.run`` (campaign, run key, dispatch) plus
the totals of ``repro.api.build`` (topology, phases, simulator and kernel
set-up) and ``repro.api.collect`` (the result's assembly)."""
from bench.harness import spans


def read(rec):
    own = spans.per_request(rec, "self_s", "repro.run")
    if own is None:
        return None
    return 1e3 * (own + spans.per_request(rec, "total_s", "repro.api."))
