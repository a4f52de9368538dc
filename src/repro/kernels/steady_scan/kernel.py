"""Windowed steady-state detection (Eq. 6/7) as a Pallas TPU kernel.

The monitor buffer is a dense (flows × history) array; each grid step loads
one (BF × H) tile into VMEM and computes trailing-window max/min/mean with
VPU row reductions.  For the production monitor (F up to 10^5 flows,
H = 128 samples) a tile is 128·128·4B = 64 KiB — bandwidth-bound, so one
pass over the buffer is optimal; fusing max/min/mean into a single read is
the entire point of the kernel (three separate jnp reductions would read
the buffer three times).  The per-flow results reduce over lanes into
``[F, 1]`` columns: a 1-D ``(BF,)`` output block compiles for a one-step
grid only, because Mosaic tiles it by 128 elements and XLA by its length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compile_cache

compile_cache.enable()

BF = 128


def _steady_kernel(hist_ref, fluct_ref, mean_ref, *, window: int, atol: float):
    h = hist_ref[...]
    H = h.shape[1]
    w = h[:, H - window:]
    mx = jnp.max(w, axis=1, keepdims=True)          # [BF, 1]
    mn = jnp.min(w, axis=1, keepdims=True)
    mean = jnp.sum(w, axis=1, keepdims=True) / window
    fluct = jnp.where(mean > 0, (mx - mn) / jnp.maximum(mean, 1e-30),
                      jnp.float32(jnp.inf))
    # dead-band (scalar detector parity): a metric pinned at <= atol is
    # steady by definition even though its relative fluctuation is 0/0
    fluct_ref[...] = jnp.where(mx <= atol, jnp.float32(0.0), fluct)
    mean_ref[...] = mean


@functools.partial(jax.jit, static_argnames=("window", "atol", "interpret"))
def steady_scan_padded(hist, *, window: int, atol: float = 0.0,
                       interpret: bool):
    """hist: [F, H] with F % BF == 0.  Returns ``(fluct, mean)`` as
    ``[F, 1]`` columns."""
    F, H = hist.shape
    assert F % BF == 0
    grid = (F // BF,)
    out = pl.pallas_call(
        functools.partial(_steady_kernel, window=window, atol=atol),
        grid=grid,
        in_specs=[pl.BlockSpec((BF, H), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((BF, 1), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((F, 1), jnp.float32)] * 2,
        interpret=interpret,
    )(hist)
    return tuple(out)
