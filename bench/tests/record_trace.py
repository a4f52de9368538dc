"""Cut the small recorded trace that ``test_trace.py`` reads from a traced
run of a fluid cell on the chip:

    python3 bench/run.py --workload gpt13b-128.fluid --seed <n> \\
        --seconds 5 --trace 1
    python3 bench/tests/record_trace.py

Keeps two milliseconds of the window, from a quarter of a millisecond
before the first fluid scan on the device: its ``XLA Ops`` and ``XLA
Modules`` events and the harness's host spans, with a ``bench.window``
span of its own.  Writes ``data/trace_fluid_v5e.json.gz`` and what the
reduction read from it when it was cut, ``data/trace_fluid_v5e.expected
.json``.
"""
import gzip
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"


def main() -> int:
    sys.path[:0] = [str(ROOT)]
    from bench.harness import trace
    events = trace.load_events(ROOT / ".bench_trace")
    lo, hi = next((e[3], e[4]) for e in events if e[2] == trace.WINDOW_SPAN)
    first = min(e[3] for e in events if e[1] == trace.MODULES_LINE
                and e[2].startswith("jit_fluid_run") and lo <= e[3] <= hi)
    t0 = first - 0.25e6
    t1 = t0 + 2e6
    cut = [e for e in events if e[2] != trace.WINDOW_SPAN
           and e[4] > t0 and e[3] < t1]
    cut.append(("/host:CPU", "python3", trace.WINDOW_SPAN, t0, t1))
    r = trace.reduce(cut)
    with gzip.open(DATA / "trace_fluid_v5e.json.gz", "wt") as f:
        json.dump(cut, f)
    expected = {k: r[k] for k in ("window_s", "busy_s", "programs")}
    (DATA / "trace_fluid_v5e.expected.json").write_text(
        json.dumps(expected, indent=1) + "\n")
    print(len(cut), "events;", expected)
    return 0


if __name__ == "__main__":
    sys.exit(main())
