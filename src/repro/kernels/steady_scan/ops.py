"""jit'd wrapper: pad flows to the tile multiple, dispatch, unpad."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.steady_scan.kernel import BF, steady_scan_padded


@partial(jax.jit, static_argnames=("window", "atol", "interpret"))
def steady_scan(hist, window: int, atol: float = 0.0,
                interpret: bool | None = None):
    """hist: [F, H] float rate history.  Returns (fluct [F], mean [F]) over
    the trailing ``window`` samples (paper Eq. 6 / Eq. 7).  ``atol`` is the
    zero-pinned-metric dead-band of the scalar/batch detectors (Eq. 6 with
    the qlen special case)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hist = jnp.asarray(hist, jnp.float32)
    F, H = hist.shape
    assert 0 < window <= H
    Fp = -(-F // BF) * BF
    pad_val = max(1.0, 2.0 * atol)   # padded rows must stay out of the band
    histp = jnp.pad(hist, ((0, Fp - F), (0, 0)), constant_values=pad_val)
    fluct, mean = steady_scan_padded(histp, window=window, atol=atol,
                                     interpret=interpret)
    return fluct[:F, 0], mean[:F, 0]
