"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints what it does on standard error, then the numbers ``correct`` was
decided by, each beside its limit, as the last lines there; the last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace
1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and ``checks`` last.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.  ``--rehearse`` runs on a machine without a TPU instead
(device metrics are left out); it refuses to run on a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.harness.cell import run_cell  # noqa: E402
from bench.harness.device import NoAccelerator  # noqa: E402
from bench.harness.spec import SpecError, load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        cell = load_cell(ROOT, args.workload)
        result, checks = run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), args.rehearse, T_START,
                                  ROOT / ".bench_trace")
    except (SpecError, NoAccelerator) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in checks:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
