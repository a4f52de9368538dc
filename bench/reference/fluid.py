"""Plain fluid model of one training iteration (DCTCP-form rate dynamics).

Each phase's flows share the fabric as a fluid: per control interval
``dt`` (the median base RTT of the phase's flows), with ``M`` the 0/1
incidence of flows on the ports they cross,

    p_l    = clip((q - K) / 2K, 0, 1)          ECN mark fraction per port
    rtt    = rtt0 + (q / C) @ M^T              queueing delay on the path
    p_f    = max over the path of p_l
    alpha' = (1 - g dt/rtt) alpha + g dt/rtt p_f
    W'     = clip(W + mss dt/rtt (1 - p_f) - p_f alpha W/2 dt/rtt,
                  mss, 2 line rtt0)
    R'     = min(W'/rtt, line)
    q'     = clip(q + (R' @ M - C) dt, 0, 64 K)

from ``R = line``, ``W = line rtt0``, ``alpha = 1``, ``q = 0``, for
``steps`` intervals.  A flow's rate is the mean of its last ``max(8,
steps/10)`` rates, its completion time ``size / max(rate, 1e3)``; a phase
ends when its slowest flow does and starts ``compute`` seconds after its
last dependency ended.

:func:`dynamics` computes in float64 with numpy.  Its float type and its
matrix product (an argument of :func:`step`) can be swapped, so that the
same equations run in a lower precision (``bench/control.py``).
"""
from __future__ import annotations

import numpy as np

from bench.reference.deployment import Fabric, Phase


def phase_arrays(fabric: Fabric, flows, mtu: float) -> dict:
    paths = [fabric.route(f.src, f.dst, f.fid) for f in flows]
    ports = sorted({p for path in paths for p in path})
    col = {p: i for i, p in enumerate(ports)}
    inc = np.zeros((len(flows), len(ports)))
    for i, path in enumerate(paths):
        inc[i, [col[p] for p in path]] = 1.0
    line = np.array([fabric.bw[path].min() for path in paths])
    prop = np.array([fabric.delay[path].sum() for path in paths])
    rtt0 = 2 * prop + (np.array([len(p) for p in paths]) + 1) * mtu / line
    return {"M": inc, "line": line, "rtt0": rtt0, "bw": fabric.bw[ports],
            "size": np.array([f.size for f in flows])}


def step(c: dict, R, W, alpha, q, dt, k, mss, g, matmul=np.matmul):
    """One control interval: the next ``(R, W, alpha, q)``."""
    M, line, rtt0, bw = c["M"], c["line"], c["rtt0"], c["bw"]
    p_l = np.clip((q - k) / (2 * k), 0.0, 1.0)
    rtt = rtt0 + matmul(q / bw, M.T)
    p_f = np.max(M * p_l[None, :], axis=1)
    dtn = dt / rtt
    alpha, W = ((1 - g * dtn) * alpha + g * dtn * p_f,
                np.clip(W + mss * dtn * (1 - p_f) - p_f * alpha * W / 2 * dtn,
                        mss, 2 * line * rtt0))
    R = np.minimum(W / rtt, line)
    q = np.clip(q + (matmul(R, M) - bw) * dt, 0.0, 64 * k)
    return R, W, alpha, q


def dynamics(a: dict, dt: float, steps: int, k: float, mss: float, g: float,
             dtype=np.float64, advance=step):
    """Rates of every step, ``[steps, flows]``; ``advance`` is :func:`step`
    or a version of it in another precision."""
    c = {n: np.asarray(a[n], dtype) for n in ("M", "line", "rtt0", "bw")}
    R, W = c["line"], c["line"] * c["rtt0"]
    alpha = np.ones_like(R)
    q = np.zeros_like(c["bw"])
    hist = []
    for _ in range(steps):
        R, W, alpha, q = advance(c, R, W, alpha, q, dt, k, mss, g)
        hist.append(R)
    return np.stack(hist)


def iteration(fabric: Fabric, phases: list[Phase], fluid: dict,
              solve=dynamics) -> dict:
    """Per-flow completion times and the iteration time.  ``solve`` is
    :func:`dynamics` or a version of it in another precision."""
    steps = fluid["steps"]
    window = max(8, steps // 10)
    fct: dict[int, float] = {}
    done_t = [0.0] * len(phases)
    for i, ph in enumerate(phases):
        start = max((done_t[d] for d in set(ph.deps)), default=0.0) \
            + ph.compute
        end = start
        if ph.flows:
            a = phase_arrays(fabric, ph.flows, fluid["mss"])
            hist = solve(a, float(np.median(a["rtt0"])), steps,
                         fluid["ecn_k"], fluid["mss"], fluid["g"])
            rates = np.asarray(hist[-window:], np.float64).mean(axis=0)
            for f, r in zip(ph.flows, rates):
                fct[f.fid] = f.size / max(float(r), 1e3)
                end = max(end, start + fct[f.fid])
        done_t[i] = end
    return {"fct": fct, "iteration_time": max(done_t)}
