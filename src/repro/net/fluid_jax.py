"""Vectorized JAX rate-dynamics engine — the TPU-native adaptation of the
packet loop (DESIGN.md §3).

A partition's contention math becomes dense linear algebra on the MXU:

    link arrivals   a = R @ M          (flows × links incidence)
    queueing        q ← clip(q + (a - C)·dt, 0, B)
    path signals    p_f = max_l  M ⊙ p_l     (ECN mark fraction)
    queue delay     d_f = (q / C) @ Mᵀ
    CCA fluid step  (DCTCP / rate-AIMD forms)

Used as (a) a fast transient solver, (b) a vmappable multi-experiment sweep
engine (the TPU analogue of running independent sims on spare cores, §6.1),
and (c) the host of the fused ``cca_step`` Pallas kernel.  It is an
*approximation* of the per-packet oracle (validated to ~10% on convergence
rates) — the paper-faithful error claims all come from Wormhole-on-oracle.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.net.topology import Topology
from repro.spans import span

compile_cache.enable()

# f32-exact MXU passes: the TPU's default precision rounds matmul operands
# to bf16, which the rates and queue delays here cannot afford
_EXACT = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class FluidScenario:
    """Dense arrays describing one partition (or a padded batch slot)."""
    incidence: np.ndarray      # [F, L] float32 0/1
    line_rate: np.ndarray      # [F] bytes/s
    base_rtt: np.ndarray       # [F] s
    size: np.ndarray           # [F] bytes
    link_bw: np.ndarray        # [L] bytes/s
    ecn_k: float = 64_000.0
    mss: float = 1000.0

    @classmethod
    def from_flows(cls, topo: Topology, flows: list[tuple[int, int, int, float]],
                   mtu: float = 1000.0, ecn_k: float = 64_000.0) -> "FluidScenario":
        """flows: (fid, src, dst, size)."""
        paths = [topo.route(s, d, fid) for fid, s, d, _ in flows]
        links = sorted({l for p in paths for l in p})
        lix = {l: i for i, l in enumerate(links)}
        M = np.zeros((len(flows), len(links)), np.float32)
        for i, p in enumerate(paths):
            for l in p:
                M[i, lix[l]] = 1.0
        bw = topo.link_bw[links].astype(np.float64)
        line = np.array([topo.link_bw[p].min() for p in paths])
        prop = np.array([topo.link_delay[p].sum() for p in paths])
        rtt = 2 * prop + (np.array([len(p) for p in paths]) + 1) * mtu / line
        return cls(incidence=M, line_rate=line, base_rtt=rtt,
                   size=np.array([f[3] for f in flows], np.float64),
                   link_bw=bw, ecn_k=ecn_k, mss=mtu)


@partial(jax.jit, static_argnames=("dt", "steps", "ecn_k", "mss", "g", "use_kernel"))
def fluid_run(M, line, rtt0, size, bw, dt: float, steps: int,
              ecn_k: float = 64_000.0, mss: float = 1000.0, g: float = 1 / 16,
              use_kernel: bool = False):
    """Advance DCTCP fluid dynamics `steps` control intervals.

    Returns dict with final rates, per-flow completion estimates, rate
    history [steps, F] and queue history [steps, L]."""
    F = M.shape[0]
    if use_kernel:
        from repro.kernels.cca_step.ops import cca_step as _step_fn

    def step(carry, _):
        R, W, alpha, delivered, q = carry
        if use_kernel:
            R2, W2, alpha2, delivered2, arrivals = _step_fn(
                R, W, alpha, delivered, size, line, rtt0, M, q, bw,
                dt=dt, g=g, ecn_k=ecn_k, mss=mss)
        else:
            p_l = jnp.clip((q - ecn_k) / (2 * ecn_k), 0.0, 1.0)
            qd = jnp.matmul(q / bw, M.T, precision=_EXACT)  # [F] queue delay
            rtt = rtt0 + qd
            p_f = jnp.max(M * p_l[None, :], axis=1)    # worst hop marks
            dtn = dt / rtt                             # round-trips this step
            alpha2 = (1 - g * dtn) * alpha + g * dtn * p_f
            grow = mss * dtn * (1 - p_f)
            cut = p_f * alpha * W / 2 * dtn
            W2 = jnp.clip(W + grow - cut, mss, 2 * line * rtt0)
            active = delivered < size
            R2 = jnp.where(active, jnp.minimum(W2 / rtt, line), 0.0)
            delivered2 = jnp.minimum(delivered + R2 * dt, size)
            arrivals = jnp.matmul(R2, M, precision=_EXACT)  # [L] MXU matmul
        q2 = jnp.clip(q + (arrivals - bw) * dt, 0.0, 64 * ecn_k)
        return (R2, W2, alpha2, delivered2, q2), (R2, q2)

    R0 = line
    W0 = line * rtt0
    init = (R0, W0, jnp.ones(F), jnp.zeros(F), jnp.zeros_like(bw))
    (R, W, alpha, delivered, q), (rate_hist, q_hist) = jax.lax.scan(
        step, init, None, length=steps)
    return {"rates": R, "delivered": delivered, "queues": q,
            "rate_hist": rate_hist, "queue_hist": q_hist}


def fluid_converged_rates(scn: FluidScenario, dt: float | None = None,
                          steps: int = 400, use_kernel: bool = False):
    """Converged per-flow rates + convergence time estimate via the steady
    detector over the simulated rate history.

    Spans: ``repro.fluid.launch`` (the inputs' transfers and the scan's
    dispatch), ``repro.fluid.fetch`` (waits for the device and copies the
    rate history back), ``repro.fluid.converge`` (the rest)."""
    dt = dt if dt is not None else float(np.median(scn.base_rtt))
    # transient solve: rates are the question, so flows are unbounded here
    # (completion handling stays with the caller / the event kernel)
    unbounded = np.full_like(scn.size, np.inf)
    with span("repro.fluid.launch"):
        out = fluid_run(jnp.asarray(scn.incidence), jnp.asarray(scn.line_rate),
                        jnp.asarray(scn.base_rtt), jnp.asarray(unbounded),
                        jnp.asarray(scn.link_bw), dt, steps,
                        ecn_k=scn.ecn_k, mss=scn.mss, use_kernel=use_kernel)
    with span("repro.fluid.fetch"):
        hist = np.asarray(out["rate_hist"])            # [steps, F]
    with span("repro.fluid.converge"):
        w = max(8, steps // 10)
        mx = hist[-w:].max(0)
        mn = hist[-w:].min(0)
        mean = hist[-w:].mean(0)
        fluct = np.where(mean > 0, (mx - mn) / np.maximum(mean, 1e-9), np.inf)
        # first step where every flow's trailing window is within 5%
        t_conv = steps * dt
        for t in range(w, steps):
            win = hist[t - w:t]
            m = win.mean(0)
            fl = np.where(m > 0, (win.max(0) - win.min(0)) / np.maximum(m, 1e-9), np.inf)
            if (fl < 0.05).all():
                t_conv = t * dt
                break
    return {"rates": mean, "fluct": fluct, "t_conv": t_conv, "hist": hist}


def sweep(scenarios: list[FluidScenario], dt: float, steps: int):
    """Multi-experiment parallelism: vmap over a padded batch of scenarios
    (the TPU analogue of Unison's spare-core experiments, §2.1)."""
    F = max(s.incidence.shape[0] for s in scenarios)
    L = max(s.incidence.shape[1] for s in scenarios)

    def pad(s: FluidScenario):
        M = np.zeros((F, L), np.float32)
        M[:s.incidence.shape[0], :s.incidence.shape[1]] = s.incidence
        def p1(x, n, fill):
            out = np.full(n, fill, np.float64)
            out[:len(x)] = x
            return out
        return (M, p1(s.line_rate, F, 1.0), p1(s.base_rtt, F, 1e-5),
                p1(s.size, F, 0.0), p1(s.link_bw, L, 1e12))

    Ms, lines, rtts, sizes, bws = (jnp.asarray(np.stack(x)) for x in
                                   zip(*[pad(s) for s in scenarios]))
    fn = jax.vmap(lambda M, l, r, s, b: fluid_run(M, l, r, s, b, dt, steps))
    return fn(Ms, lines, rtts, sizes, bws)


def sweep_converged_rates(scenarios: list[FluidScenario], dt: float = 1e-5,
                          steps: int = 200, window: int | None = None,
                          bounded: bool = False) -> list[np.ndarray]:
    """One vmapped sweep → per-scenario converged rates (trailing-window
    means), unpadded back to each scenario's true flow count.  With
    ``bounded=False`` (the default) flow sizes are lifted to ∞ so the
    answer is the contention equilibrium, not a completion artifact."""
    if not bounded:
        scenarios = [dataclasses.replace(
            s, size=np.full_like(np.asarray(s.size, np.float64), np.inf))
            for s in scenarios]
    out = sweep(scenarios, dt=dt, steps=steps)
    hist = np.asarray(out["rate_hist"])               # [n_scn, steps, F_pad]
    w = window if window is not None else max(8, steps // 10)
    means = hist[:, -w:, :].mean(axis=1)
    return [means[i, :s.incidence.shape[0]] for i, s in enumerate(scenarios)]
