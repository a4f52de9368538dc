"""hybrid.solves: max-min solves per traced request (the count of
``repro.hybrid.solve`` spans)."""
from bench.harness import spans


def read(rec):
    return spans.per_request(rec, "n", "repro.hybrid.solve")
