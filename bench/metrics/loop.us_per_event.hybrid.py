"""loop.us_per_event.hybrid: ``loop.us_per_event`` of the hybrid cell (it
moves ``iter_s.hybrid``): microseconds of the engine's loop
(``RunResult.wall_time``) per event it processed, over the window."""


def read(rec):
    reqs = rec["requests"]
    events = sum(r["events"] for r in reqs)
    return 1e6 * sum(r["wall_s"] for r in reqs) / events if events else None
