"""api.build_s.hybrid: ``api.build_s`` of the hybrid cell (it moves
``iter_s.hybrid``): host seconds a request spends in ``repro.api.run``
outside the engine's own loop (``RunResult.wall_time``).  Mean per
request."""


def read(rec):
    reqs = rec["requests"]
    return sum(r["host_s"] - r["wall_s"] for r in reqs) / len(reqs)
