"""jit'd public wrapper: pads (flows, links) to kernel tile multiples, lays
the state out as the kernel's rows/columns, dispatches, unpads.  With
``interpret=None`` the kernel compiles on a TPU backend and runs in
interpret mode elsewhere (CPU validation)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.cca_step.kernel import BF, cca_step_padded

_SUBLANES = 8   # link-axis pad multiple (float32 sublane tile)


def _pad_to(x, n, fill=0.0):
    if x.shape[0] == n:
        return x
    return jnp.pad(x, (0, n - x.shape[0]), constant_values=fill)


@partial(jax.jit, static_argnames=("dt", "g", "ecn_k", "mss", "interpret"))
def cca_step(R, W, alpha, delivered, size, line, rtt0, M, q, bw, *,
             dt: float, g: float = 1 / 16, ecn_k: float = 64_000.0,
             mss: float = 1000.0, interpret: bool | None = None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    F, L = M.shape
    Fp = -(-F // BF) * BF
    Lp = -(-L // _SUBLANES) * _SUBLANES
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    row = lambda x, fill=0.0: _pad_to(f32(x), Fp, fill)[None, :]
    col = lambda x, fill=0.0: _pad_to(f32(x), Lp, fill)[:, None]
    args = (
        row(R), row(W, fill=mss), row(alpha), row(delivered),
        row(size),                             # padded flows: size 0 -> idle
        row(line, fill=1.0), row(rtt0, fill=1.0),
        jnp.pad(f32(M), ((0, Fp - F), (0, Lp - L))).T,
        col(q), col(bw, fill=1.0),
    )
    R2, W2, a2, d2, arr = cca_step_padded(
        *args, dt=dt, g=g, ecn_k=ecn_k, mss=mss, interpret=interpret)
    return R2[0, :F], W2[0, :F], a2[0, :F], d2[0, :F], arr[:L, 0]
