"""loop.self_us_per_event: microseconds of the engine's loop outside the
kernel per event it processed: the self time of ``repro.engine.loop`` (the
interval ``RunResult.wall_time`` measures, less the ``repro.kernel.*``
hooks inside it) over the events of the traced requests."""
from bench.harness import spans


def read(rec):
    loop = spans.per_request(rec, "self_s", "repro.engine.loop")
    events = spans.events_per_request(rec)
    if loop is None or not events:
        return None
    return 1e6 * loop / events
