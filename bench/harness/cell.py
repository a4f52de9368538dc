"""One run of one cell: set-up, the measured window, the reference, the
comparison and the metrics.

The generator is general: a cell is its configuration (the deployment,
with the stage rule it names) and its traffic mix (the backend, its
options, and the variant a seed draws).  The seed draws
``variant.stragglers.count`` straggler ranks whose compute is stretched by
``variant.stragglers.factor``; every request of the run simulates that one
variant from fresh simulator state, through the users' entry
``repro.api.run``.  The loop is closed with one client: the
next request starts when the previous one has returned.
"""
from __future__ import annotations

import contextlib
import gc
import pathlib
import sys
import time

from bench.harness import compare, device, trace
from bench.harness.spec import Cell
from bench.reference import deployment, fluid, packet

# the traced run profiles the window's first requests, up to this long: a
# second of the fluid path records some 500,000 device operations
TRACE_SECONDS = 1.0


def variant(cell: Cell, seed: int):
    """The program's scenario and the reference's phases for ``seed``."""
    from repro.api import training_scenario
    v = cell.mix["variant"]["stragglers"]
    cfg = cell.config
    ranks = deployment.straggler_ranks(
        seed, v["count"], cfg["fabric"]["n_servers"]
        * cfg["fabric"]["gpus_per_server"])
    scn = training_scenario(**cfg["preset"], chaos=[
        {"kind": "straggler", "ranks": ranks, "factor": v["factor"]}])
    phases = deployment.gpipe_phases(cfg, cell.stage_loads(cfg),
                                     {r: v["factor"] for r in ranks})
    return scn, phases, ranks


def reference(cell: Cell, phases) -> dict:
    """Run the plain reference the mix names over ``phases``."""
    kind = cell.mix["reference"]
    fab = deployment.Fabric.from_config(cell.config)
    t0 = time.perf_counter()
    if kind == "packet":
        out = packet.PacketReference(fab, phases, cell.config["sim"],
                                     cell.config["hpcc"]).run()
    elif kind == "fluid":
        out = fluid.iteration(fab, phases, cell.config["fluid"])
        out["events"] = None
    else:
        raise ValueError(f"unknown reference {kind!r}")
    out["kind"] = kind
    out["seconds"] = time.perf_counter() - t0
    return out


def request_record(res, host_s: float) -> dict:
    rec = {"host_s": host_s, "wall_s": res.wall_time,
           "events": res.events_processed}
    gran = res.extras.get("granularity")
    if gran:
        rec["packet_lane_events"] = gran["packet_lane_events"]
        rec["flow_lane_events"] = gran["flow_lane_events"]
    return rec


def timed_request(run, scn, cell: Cell):
    import jax
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.run"):
        res = run(scn, backend=cell.mix["backend"], **cell.mix["opts"])
    return res, time.perf_counter() - t0


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             rehearse: bool, t_start: float, profile_dir: pathlib.Path,
             api_run=None) -> tuple[dict, list[str]]:
    """``(result line, check lines)`` of one run; ``t_start`` is when the
    process started, ``profile_dir`` where a traced run writes its trace.
    ``api_run`` replaces ``repro.api.run`` (tests break the timed path
    through it)."""
    devices = device.bring_up(cell.chips, allow_cpu=rehearse)
    import jax
    import jax.numpy as jnp
    stats = device.CompileStats()
    if api_run is None:
        from repro.api import run as api_run
    log = sys.stderr
    scn, phases, ranks = variant(cell, seed)
    print(f"cell {cell.name}: seed {seed}, stragglers {ranks}, "
          f"backend {cell.mix['backend']} {cell.mix['opts']}", file=log)

    # the harness's own device operation, at both ends of the traced part
    # of the window: a cell whose program puts nothing on the device still
    # has a trace that is not empty (the profiler can miss the first one)
    zero = jnp.zeros((), jnp.float32)
    marker = jax.jit(lambda x: x + 1)

    def mark():
        marker(zero).block_until_ready()

    mark()
    with jax.profiler.TraceAnnotation("bench.warmup"):
        timed_request(api_run, scn, cell)
    gc.collect()
    compiles_setup = stats.compiles
    setup_s = time.perf_counter() - t_start

    answers, records = [], []
    traced_part = contextlib.ExitStack()
    if traced:
        traced_part.enter_context(trace.capture(profile_dir))
    traced_part.enter_context(jax.profiler.TraceAnnotation("bench.window"))
    mark()
    traced_requests = 0
    w0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.request"):
            res, host_s = timed_request(api_run, scn, cell)
        answers.append((res.fcts, res.iteration_time))
        records.append(request_record(res, host_s))
        elapsed = time.perf_counter() - w0
        if not traced_requests and (elapsed >= TRACE_SECONDS
                                    or elapsed >= seconds):
            mark()
            traced_part.close()
            traced_requests = len(records)
        if elapsed >= seconds:
            break
    window_s = time.perf_counter() - w0
    compiles_window = stats.compiles - compiles_setup
    memory = device.memory_peak_bytes(devices)

    ref = reference(cell, phases)
    print(f"reference: {ref['kind']}, {ref['seconds']:.3f} s, events "
          f"{ref['events']}", file=log)
    rows = [compare.numbers(f, it, ref["fct"], ref["iteration_time"])
            for f, it in answers]
    compared = compare.worst(rows)
    correct, checks = compare.judge(compared, cell.limits)
    failed = sum(1 for r in rows
                 if not compare.judge(r, cell.limits)[0])

    reduced = None
    if traced and not rehearse:
        reduced = trace.reduce(trace.load_events(profile_dir))
    rec = {"setup_s": setup_s, "window_s": window_s, "requests": records,
           "traced_requests": traced_requests, "reference": ref,
           "compared": compared, "trace": reduced}
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        if rehearse and m["source"] == "device_trace":
            continue
        value = cell.readers[m["name"]](rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = device.describe(devices)
    dev["memory_peak_bytes"] = memory
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    print(f"setup {setup_s:.3f} s ({compiles_setup} compiles, "
          f"{stats.hits}/{stats.requests} persistent-cache hits); window "
          f"{window_s:.3f} s, {len(records)} requests, {compiles_window} "
          f"compiles in the window", file=log)
    lines = [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
             for k, c in checks.items()]
    return result, lines

