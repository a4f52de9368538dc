"""loop.self_us_per_event.hybrid: ``loop.self_us_per_event`` of the hybrid
cell (it moves ``iter_s.hybrid``): self time of ``repro.engine.loop`` per
event of the traced requests, in microseconds."""
from bench.harness import spans


def read(rec):
    loop = spans.per_request(rec, "self_s", "repro.engine.loop")
    events = spans.events_per_request(rec)
    if loop is None or not events:
        return None
    return 1e6 * loop / events
