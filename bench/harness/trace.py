"""Profiler traces: capture one, and reduce it to device busy time,
per-program device time and idle time by what the host was doing.

The reduction works on plain event tuples ``(plane, line, name, start_ns,
end_ns)``, so it can be checked on a small recorded trace
(``bench/tests/data/``) as well as run on a fresh one.

* Device planes are those named ``/device:TPU:<n>``.  Busy time is the
  union of the intervals of their ``XLA Modules`` (whole programs) and
  ``XLA Ops`` (the operations inside them) events inside the window,
  averaged over the device planes.  A program too small to record
  operations still counts.
* A program's device time is the sum of its events on the ``XLA Modules``
  line, by name with the trailing ``(<id>)`` dropped; an operation's, the
  sum of its ``XLA Ops`` events, by the name before `` = ``.
* Host spans are the events named ``bench.*`` (``jax.profiler.
  TraceAnnotation`` in the harness) on any host plane.  The window is the
  ``bench.window`` span.  Each idle stretch of the device is charged to the
  innermost host span that covers it.
"""
from __future__ import annotations

import collections
import contextlib
import pathlib
import re
import shutil

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def capture(log_dir: pathlib.Path):
    """Profile the body into ``log_dir`` (emptied first).  Python calls
    are not traced: the simulators make millions of them."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_events(log_dir: pathlib.Path) -> list[tuple]:
    """The events of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(files)}")
    out = []
    for plane in ProfileData.from_file(str(files[0])).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                start = float(ev.start_ns)
                out.append((plane.name, line.name, ev.name, start,
                            start + float(ev.duration_ns)))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted cover of ``intervals``."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv
            if min(b, hi) > max(a, lo)]


def _gaps(busy, lo, hi):
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def reduce(events: list[tuple], top: int = 10) -> dict:
    """Busy and window seconds, device seconds by program, the device
    operations that took most time and idle seconds by host span."""
    spans = [(e[3], e[4], e[2]) for e in events
             if not DEVICE_PLANE.match(e[0]) and e[2].startswith(SPAN_PREFIX)]
    windows = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    lo, hi = windows[0]
    planes = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})
    busy_s, programs = 0.0, collections.Counter()
    ops = collections.Counter()
    idle = collections.Counter()
    inner = sorted(((a, b, n) for a, b, n in spans if n != WINDOW_SPAN),
                   key=lambda s: s[1] - s[0])      # innermost first
    for plane in planes:
        mine = [e for e in events if e[0] == plane]
        busy = union(_clip([(e[3], e[4]) for e in mine], lo, hi))
        busy_s += sum(b - a for a, b in busy) * 1e-9
        for e in mine:
            dur = (min(e[4], hi) - max(e[3], lo)) * 1e-9
            if dur <= 0:
                continue
            if e[1] == MODULES_LINE:
                programs[re.sub(r"\(\d+\)$", "", e[2])] += dur
            else:
                ops[e[2].split(" = ")[0]] += dur
        for a, b in _gaps(busy, lo, hi):
            # cut the gap where spans begin or end inside it; each piece
            # goes to the innermost span that covers it
            cuts = sorted({a, b, *(x for s in inner for x in s[:2]
                                   if a < x < b)})
            for c0, c1 in zip(cuts, cuts[1:]):
                owner = next((n for s0, s1, n in inner
                              if s0 <= c0 and c1 <= s1), WINDOW_SPAN)
                idle[owner] += (c1 - c0) * 1e-9
    n = max(len(planes), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s / n,
        "devices": len(planes),
        "programs": {k: v / n for k, v in programs.items()},
        "device_ops": [[k, v / n] for k, v in ops.most_common(top)],
        "idle_gaps": [[k, v / n] for k, v in idle.most_common(top)],
    }
