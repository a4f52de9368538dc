"""wormhole.event_ratio: events the wormhole backend processed per request
over the events of the packet reference on the same variant."""


def read(rec):
    ref = rec["reference"]["events"]
    if not ref:
        return None
    reqs = rec["requests"]
    return sum(r["events"] for r in reqs) / len(reqs) / ref
