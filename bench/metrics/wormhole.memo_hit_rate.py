"""wormhole.memo_hit_rate: share of the wormhole kernel's memo queries
that replayed a stored transient: ``repro.wormhole.replay`` spans over
``repro.wormhole.memo`` spans in the traced requests."""
from bench.harness import spans


def read(rec):
    memo = spans.per_request(rec, "n", "repro.wormhole.memo")
    if not memo:
        return None
    return spans.per_request(rec, "n", "repro.wormhole.replay") / memo
