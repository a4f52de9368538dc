"""The program's own spans in a traced run: how often each ``repro.*`` span
(``repro.spans`` in the program) ran inside the window, its total and its
self time.

``trace.py`` reduces the harness's ``bench.*`` spans and the device's
operations; this reads the program's spans from the same trace file, once
a run, and keeps them in the run record beside that reduction
(``rec["trace"]["spans"]``), where the per-layer metrics read them.

* A span counts when it lies in the ``bench.window`` span; its total is its
  duration clipped to the window.
* Its self time is the total less the union of the spans directly inside
  it on the same plane and line (one thread), each clipped alike.
"""
from __future__ import annotations

import collections
import pathlib

from bench.harness import trace

PREFIX = "repro."
# where bench/run.py has a traced run write its trace
TRACE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"


def load_events(log_dir: pathlib.Path) -> list[tuple]:
    """The host spans (``bench.*`` and ``repro.*``) of the one trace under
    ``log_dir``, in ``trace.load_events``' tuple form."""
    from jax.profiler import ProfileData
    files = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(files)}")
    out = []
    for plane in ProfileData.from_file(str(files[0])).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith((trace.SPAN_PREFIX, PREFIX)):
                    start = float(ev.start_ns)
                    out.append((plane.name, line.name, ev.name, start,
                                start + float(ev.duration_ns)))
    return out


def reduce(events: list[tuple]) -> dict:
    """``{name: {"n", "total_s", "self_s"}}`` of every ``repro.*`` span in
    the window."""
    windows = [(e[3], e[4]) for e in events if e[2] == trace.WINDOW_SPAN
               and not trace.DEVICE_PLANE.match(e[0])]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {trace.WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    lo, hi = windows[0]
    threads = collections.defaultdict(list)
    for plane, line, name, a, b in events:
        if trace.DEVICE_PLANE.match(plane) or a > hi or b < lo:
            continue
        threads[plane, line].append((max(a, lo), min(b, hi), name))
    out: dict[str, dict] = {}
    for spans in threads.values():
        # outer spans first where two start together
        spans.sort(key=lambda s: (s[0], -s[1]))
        inside: list[list] = [[] for _ in spans]
        stack: list[int] = []
        for i, (a, b, _) in enumerate(spans):
            while stack and spans[stack[-1]][1] <= a:
                stack.pop()
            if stack:
                inside[stack[-1]].append((a, min(b, spans[stack[-1]][1])))
            stack.append(i)
        for (a, b, name), kids in zip(spans, inside):
            if not name.startswith(PREFIX):
                continue
            covered = sum(k1 - k0 for k0, k1 in trace.union(kids))
            s = out.setdefault(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
            s["n"] += 1
            s["total_s"] += (b - a) * 1e-9
            s["self_s"] += (b - a - covered) * 1e-9
    return out


def of(rec: dict) -> dict | None:
    """The program's spans of a run record; None without a trace or where
    the program recorded none."""
    tr = rec["trace"]
    if not tr or not rec["traced_requests"]:
        return None
    if "spans" not in tr:
        tr["spans"] = reduce(load_events(TRACE_DIR))
    return tr["spans"] or None


def per_request(rec: dict, key: str, *names: str) -> float | None:
    """The sum of ``key`` (``n``, ``total_s`` or ``self_s``) over the spans
    whose names start with one of ``names``, per traced request; 0 where
    the program recorded spans but none of these."""
    spans = of(rec)
    if spans is None:
        return None
    return sum(s[key] for name, s in spans.items()
               if name.startswith(names)) / rec["traced_requests"]


def events_per_request(rec: dict) -> float:
    """Events the engine processed per traced request."""
    n = rec["traced_requests"]
    return sum(r["events"] for r in rec["requests"][:n]) / n if n else 0.0
