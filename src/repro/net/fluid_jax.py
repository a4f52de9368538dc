"""Vectorized JAX rate-dynamics engine — the TPU-native adaptation of the
packet loop (DESIGN.md §3).

A partition's contention math becomes dense linear algebra on the MXU:

    link arrivals   a = R @ M          (flows × links incidence)
    queueing        q ← clip(q + (a - C)·dt, 0, B)
    path signals    p_f = max_l  M ⊙ p_l     (ECN mark fraction)
    queue delay     d_f = (q / C) @ Mᵀ
    CCA fluid step  (DCTCP / rate-AIMD forms)

Used as (a) a fast transient solver, one device program for every phase
of a training iteration or every scenario of a sweep
(``converged_phase_rates``: the TPU analogue of running independent sims
on spare cores, §6.1), and (b) the host of the fused ``cca_step`` Pallas
kernel.  It is a coarse *approximation* of the per-packet oracle: on the
128-GPU GPT row (flow scale 1/256) the fluid backend's mean FCT error
against packet is 54.8% — the paper-faithful error claims all come from
Wormhole-on-oracle.
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


def _dynamics(M, line, rtt0, size, bw, dt, steps: int, ecn_k: float,
             mss: float, g: float, use_kernel: bool = False):
    """The DCTCP fluid step scanned over `steps` control intervals: the one
    copy of the equations.  ``dt`` may be traced (one per vmapped phase)
    except with ``use_kernel``, whose Pallas step takes it as a constant.

    Returns ``((R, W, alpha, delivered, q), (rate_hist, q_hist))``."""
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
    return jax.lax.scan(step, init, None, length=steps)


@partial(jax.jit, static_argnames=("dt", "steps", "ecn_k", "mss", "g", "use_kernel"))
def fluid_run(M, line, rtt0, size, bw, dt: float, steps: int,
              ecn_k: float = 64_000.0, mss: float = 1000.0, g: float = 1 / 16,
              use_kernel: bool = False):
    """Advance DCTCP fluid dynamics `steps` control intervals.

    Returns dict with final rates, per-flow completion estimates, rate
    history [steps, F] and queue history [steps, L]."""
    (R, W, alpha, delivered, q), (rate_hist, q_hist) = _dynamics(
        M, line, rtt0, size, bw, dt, steps, ecn_k, mss, g, use_kernel)
    return {"rates": R, "delivered": delivered, "queues": q,
            "rate_hist": rate_hist, "queue_hist": q_hist}


@partial(jax.jit, static_argnames=("steps", "window", "ecn_k", "mss", "g"))
def fluid_run_phases(M, line, rtt0, size, bw, dt, steps: int, window: int,
                     ecn_k: float = 64_000.0, mss: float = 1000.0,
                     g: float = 1 / 16):
    """Every phase of a run in one program: :func:`_dynamics` vmapped over
    padded ``[P, F, L]`` incidences, ``[P, F]`` line rates, base RTTs and
    sizes, ``[P, L]`` link rates and a ``[P]`` ``dt``.  Returns only the
    trailing ``window`` rates of each phase, ``[P, window, F]``."""
    def one(M, line, rtt0, size, bw, dt):
        _, (rate_hist, _) = _dynamics(M, line, rtt0, size, bw, dt, steps,
                                      ecn_k, mss, g)
        return rate_hist[-window:]
    return jax.vmap(one)(M, line, rtt0, size, bw, dt)


def fluid_converged_rates(scn: FluidScenario, dt: float | None = None,
                          steps: int = 400, use_kernel: bool = False):
    """Converged per-flow rates + convergence time estimate via the steady
    detector over the simulated rate history."""
    dt = dt if dt is not None else float(np.median(scn.base_rtt))
    # transient solve: rates are the question, so flows are unbounded here
    # (completion handling stays with the caller / the event kernel)
    unbounded = np.full_like(scn.size, np.inf)
    out = fluid_run(jnp.asarray(scn.incidence), jnp.asarray(scn.line_rate),
                    jnp.asarray(scn.base_rtt), jnp.asarray(unbounded),
                    jnp.asarray(scn.link_bw), dt, steps,
                    ecn_k=scn.ecn_k, mss=scn.mss, use_kernel=use_kernel)
    hist = np.asarray(out["rate_hist"])                # [steps, F]
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


def pad_scenarios(scenarios: list[FluidScenario]):
    """Stack scenarios padded to their largest flow and link counts:
    ``(M [P, F, L] float32, line, rtt0 [P, F], bw [P, L])``.

    A padded flow has no links (a zero incidence row), line rate 1 and
    base RTT 1e-5; a padded link has 1e12 B/s and never queues.  So every
    sum and max over a real flow's path or a real link's flows gains exact
    zeros, and the real entries compute as unpadded."""
    F = max(s.incidence.shape[0] for s in scenarios)
    L = max(s.incidence.shape[1] for s in scenarios)
    M = np.zeros((len(scenarios), F, L), np.float32)

    def p1(xs, n, fill):
        out = np.full((len(xs), n), fill, np.float64)
        for i, x in enumerate(xs):
            out[i, :len(x)] = x
        return out

    for i, s in enumerate(scenarios):
        M[i, :s.incidence.shape[0], :s.incidence.shape[1]] = s.incidence
    return (M, p1([s.line_rate for s in scenarios], F, 1.0),
            p1([s.base_rtt for s in scenarios], F, 1e-5),
            p1([s.link_bw for s in scenarios], L, 1e12))


def converged_phase_rates(scenarios: list[FluidScenario], steps: int = 200,
                          dt: float | None = None):
    """Converged per-flow rates of independent phases (or scenarios of a
    sweep), all solved in one device program (:func:`fluid_run_phases`):
    one transfer of the padded inputs, one dispatch, one fetch of the
    trailing windows.  A phase's
    rates are the mean of its last ``max(8, steps // 10)`` rates, as
    :func:`fluid_converged_rates` gives them; flows are unbounded.
    ``dt=None`` gives each phase the median of its base RTTs.

    Returns ``(rates per scenario, padding share)``, the share being the
    part of the padded ``P x F x L`` incidence that is padding.  Spans:
    ``repro.fluid.launch`` (stacking, transfers, dispatch),
    ``repro.fluid.fetch`` (waits for the device and copies the windows
    back), ``repro.fluid.converge`` (window means, unpadding)."""
    consts = {(s.ecn_k, s.mss) for s in scenarios}
    if len(consts) != 1:
        raise ValueError(f"phases differ in (ecn_k, mss): {sorted(consts)}")
    (ecn_k, mss), = consts
    w = max(8, steps // 10)
    with span("repro.fluid.launch"):
        M, line, rtt0, bw = pad_scenarios(scenarios)
        dts = [float(np.median(s.base_rtt)) if dt is None else dt
               for s in scenarios]
        # transient solve: rates are the question, so flows are unbounded
        args = jax.device_put(tuple(np.asarray(x, np.float32) for x in (
            M, line, rtt0, np.full(line.shape, np.inf), bw, dts)))
        out = fluid_run_phases(*args, steps=steps, window=w,
                               ecn_k=ecn_k, mss=mss)
    with span("repro.fluid.fetch"):
        win = np.asarray(out)                          # [P, w, F]
    with span("repro.fluid.converge"):
        means = win.mean(1)
        rates = [means[i, :s.incidence.shape[0]]
                 for i, s in enumerate(scenarios)]
        pad_share = 1.0 - sum(s.incidence.size for s in scenarios) / M.size
    return rates, pad_share
