"""api.build_s: host seconds a request spends in ``repro.api.run`` outside
the engine's own loop (``RunResult.wall_time``): campaign and run key,
topology, phase DAG, simulator set-up and result collection.  Mean per
request."""


def read(rec):
    reqs = rec["requests"]
    return sum(r["host_s"] - r["wall_s"] for r in reqs) / len(reqs)
