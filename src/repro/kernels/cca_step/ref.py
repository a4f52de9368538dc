"""Pure-jnp oracle for the fused DCTCP fluid step (matches
repro.net.fluid_jax.fluid_run's inline branch)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EXACT = jax.lax.Precision.HIGHEST


def cca_step_ref(R, W, alpha, delivered, size, line, rtt0, M, q, bw,
                 *, dt: float, g: float = 1 / 16, ecn_k: float = 64_000.0,
                 mss: float = 1000.0):
    p_l = jnp.clip((q - ecn_k) / (2 * ecn_k), 0.0, 1.0)
    qd = jnp.matmul(q / bw, M.T, precision=_EXACT)
    rtt = rtt0 + qd
    p_f = jnp.max(M * p_l[None, :], axis=1)
    dtn = dt / rtt
    alpha2 = (1 - g * dtn) * alpha + g * dtn * p_f
    grow = mss * dtn * (1 - p_f)
    cut = p_f * alpha * W / 2 * dtn
    W2 = jnp.clip(W + grow - cut, mss, 2 * line * rtt0)
    active = delivered < size
    R2 = jnp.where(active, jnp.minimum(W2 / rtt, line), 0.0)
    delivered2 = jnp.minimum(delivered + R2 * dt, size)
    arrivals = jnp.matmul(R2, M, precision=_EXACT)
    return R2, W2, alpha2, delivered2, arrivals
