"""hybrid.packet_lane_ratio: packet-lane events of the hybrid backend per
request (``extras["granularity"]``) over the events of the packet
reference on the same variant."""


def read(rec):
    ref = rec["reference"]["events"]
    lanes = [r["packet_lane_events"] for r in rec["requests"]
             if "packet_lane_events" in r]
    if not ref or not lanes:
        return None
    return sum(lanes) / len(lanes) / ref
