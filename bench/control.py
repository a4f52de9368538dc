"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--rehearse]

For each seed: the variant's reference, one answer of the program as the
cell's requests give it (the sound reading) and one answer of the cell's
control (the upper reading), each compared with the reference.  Prints one
JSON line per seed.

The control is named in the cell's traffic mix:

* ``{"kind": "program", "opts": {...}}`` — the program with those engine
  options, a path of its own that breaks the configuration's guarantee;
* ``{"kind": "reference", "precision": "high"}`` — the fluid reference
  in float32 with each matrix product taken as three bfloat16 passes
  (``Precision.HIGH``), the step below the float32-exact products
  (``Precision.HIGHEST``) the configuration states.  It is written out
  in numpy on the host, so that it computes the same on every machine: on
  a TPU, XLA lowers these matrix-vector products to float32 reductions
  and drops a cast to bfloat16 around them, so the program's own
  precision setting would not reach them.

Not part of a benchmark run.  Like ``run.py`` it needs a TPU unless given
``--rehearse``.
"""
import argparse
import functools
import json
import pathlib
import sys
import time

import ml_dtypes
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.harness import compare, device  # noqa: E402
from bench.harness.cell import reference, variant  # noqa: E402
from bench.harness.spec import load_cell  # noqa: E402
from bench.reference import deployment, fluid  # noqa: E402


def _bf16(x):
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def high_pass(a, b):
    """``a @ b`` in float32 as ``Precision.HIGH`` computes it: each
    operand split into a bfloat16 high part and a bfloat16 remainder, and
    the three products hi*hi + hi*lo + lo*hi accumulated in float32."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (ah @ bh + ah @ bl + al @ bh).astype(np.float32)


def control_answer(cell, scn, phases, api_run) -> dict:
    """``{"fct", "iteration_time"}`` of the cell's control."""
    ctl = cell.mix["control"]
    if ctl["kind"] == "program":
        res = api_run(scn, backend=cell.mix["backend"], **ctl["opts"])
        return {"fct": res.fcts, "iteration_time": res.iteration_time}
    if ctl["kind"] == "reference" and ctl["precision"] == "high":
        solve = functools.partial(
            fluid.dynamics, dtype=np.float32,
            advance=functools.partial(fluid.step, matmul=high_pass))
        fab = deployment.Fabric.from_config(cell.config)
        return fluid.iteration(fab, phases, cell.config["fluid"], solve)
    raise ValueError(f"unknown control {ctl}")


def readings(cell, seed: int, api_run) -> dict:
    scn, phases, ranks = variant(cell, seed)
    ref = reference(cell, phases)
    t0 = time.perf_counter()
    res = api_run(scn, backend=cell.mix["backend"], **cell.mix["opts"])
    t1 = time.perf_counter()
    ctl = control_answer(cell, scn, phases, api_run)
    t2 = time.perf_counter()
    return {
        "seed": seed, "stragglers": ranks,
        "sound": compare.numbers(res.fcts, res.iteration_time, ref["fct"],
                                 ref["iteration_time"]),
        "control": compare.numbers(ctl["fct"], ctl["iteration_time"],
                                   ref["fct"], ref["iteration_time"]),
        "seconds": {"reference": ref["seconds"], "sound": t1 - t0,
                    "control": t2 - t1}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    devices = device.bring_up(cell.chips, allow_cpu=args.rehearse)
    from repro.api import run
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(cell, seed, run)
        out["device"] = device.describe(devices)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
