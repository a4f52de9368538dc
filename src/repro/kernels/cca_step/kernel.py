"""Fused DCTCP-fluid CCA step as a Pallas TPU kernel.

Layout: flows lie along the lanes.  Per-flow state is a ``[1, F]`` row and
the incidence is stored links-major, ``M_t [L, F]``; each grid step holds
one ``(L × BF)`` incidence tile and ``BF`` flows of every state row in
VMEM, together with the full ``[L, 1]`` queue/bandwidth columns.  Per-flow
math is lane-dense VPU work; the per-flow path reductions (queue delay,
worst-hop mark) reduce over sublanes, and the link-arrival column reduces
over lanes.  Link arrivals accumulate across the sequential grid into one
``[L, 1]`` output block (first block initialises, later blocks add).

Every operand is 2-D: Mosaic lays a 1-D block out in 128-element tiles
while XLA tiles a longer 1-D array by its full length, so 1-D blocks only
compile for a one-step grid.

VMEM budget per grid step (f32): incidence tile L·128·4 B — for L ≤ 4096
that is ≤ 2 MiB (4 MiB double-buffered), inside the 16 MiB default scoped
VMEM of a v5e core, leaving room for the [1, BF] rows and [L, 1] columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compile_cache

compile_cache.enable()

BF = 128  # flow block: one lane tile


def _cca_step_kernel(R_ref, W_ref, alpha_ref, dlv_ref, size_ref, line_ref,
                     rtt0_ref, M_ref, q_ref, bw_ref,
                     R2_ref, W2_ref, alpha2_ref, dlv2_ref, arr_ref,
                     *, dt: float, g: float, ecn_k: float, mss: float):
    i = pl.program_id(0)
    q = q_ref[...]                                  # [L, 1]
    bw = bw_ref[...]
    M = M_ref[...]                                  # [L, BF]
    p_l = jnp.clip((q - ecn_k) / (2 * ecn_k), 0.0, 1.0)
    qd = jnp.sum(M * (q / bw), axis=0, keepdims=True)   # [1, BF]
    rtt = rtt0_ref[...] + qd
    p_f = jnp.max(M * p_l, axis=0, keepdims=True)
    dtn = dt / rtt
    alpha = alpha_ref[...]
    alpha2 = (1 - g * dtn) * alpha + g * dtn * p_f
    W = W_ref[...]
    grow = mss * dtn * (1 - p_f)
    cut = p_f * alpha * W * 0.5 * dtn
    line = line_ref[...]
    W2 = jnp.clip(W + grow - cut, mss, 2 * line * rtt0_ref[...])
    active = dlv_ref[...] < size_ref[...]
    R2 = jnp.where(active, jnp.minimum(W2 / rtt, line), 0.0)
    dlv2 = jnp.minimum(dlv_ref[...] + R2 * dt, size_ref[...])

    R2_ref[...] = R2
    W2_ref[...] = W2
    alpha2_ref[...] = alpha2
    dlv2_ref[...] = dlv2

    contrib = jnp.sum(M * R2, axis=1, keepdims=True)    # [L, 1]

    @pl.when(i == 0)
    def _init():
        arr_ref[...] = contrib

    @pl.when(i > 0)
    def _acc():
        arr_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("dt", "g", "ecn_k", "mss", "interpret"))
def cca_step_padded(R, W, alpha, delivered, size, line, rtt0, M_t, q, bw,
                    *, dt: float, g: float, ecn_k: float, mss: float,
                    interpret: bool):
    """All inputs pre-padded and 2-D: per-flow rows ``[1, F]`` with
    F % BF == 0, incidence ``M_t [L, F]`` (links × flows), per-link columns
    ``[L, 1]``.  Padded flows must have size=0, line=1, rtt0>0 so they
    stay inactive.  Returns ``(R, W, alpha, delivered)`` rows and the
    ``[L, 1]`` link arrivals."""
    L, F = M_t.shape
    assert F % BF == 0, F
    flow_spec = pl.BlockSpec((1, BF), lambda i: (0, i))
    link_spec = pl.BlockSpec((L, 1), lambda i: (0, 0))
    kernel = functools.partial(_cca_step_kernel, dt=dt, g=g, ecn_k=ecn_k, mss=mss)
    out = pl.pallas_call(
        kernel,
        grid=(F // BF,),
        in_specs=[flow_spec] * 7 + [pl.BlockSpec((L, BF), lambda i: (0, i)),
                                    link_spec, link_spec],
        out_specs=[flow_spec] * 4 + [link_spec],
        out_shape=[jax.ShapeDtypeStruct((1, F), jnp.float32)] * 4
        + [jax.ShapeDtypeStruct((L, 1), jnp.float32)],
        interpret=interpret,
    )(R, W, alpha, delivered, size, line, rtt0, M_t, q, bw)
    return tuple(out)
