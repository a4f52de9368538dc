"""Fixed-point max-min water-filling as a Pallas kernel.

One invocation holds the whole incidence plus the capacity column in VMEM
and runs the saturate-and-freeze rounds of ``ref.maxmin_ref`` on-chip, so
looping in VMEM beats ``L`` separate host-side reductions exactly the way
``steady_scan`` fused its three.

Layout: flows lie along the lanes, in chunks of ``CHUNK``.  The incidence
is ``[n_chunks, L, CHUNK]`` (links on sublanes), per-flow rates and the
active mask are ``[n_chunks, 1, CHUNK]`` rows and per-link state is an
``[L, 1]`` column, so every per-flow value is lane-dense and a round walks
the chunks with a loop over the leading axis.  A round is one pass over
the incidence: it freezes the flows that cross a saturated link and counts
them per link, and that count updates both the users and the capacities
(``users`` are small integers in float32, so the update is exact and the
result stays bit-equal to the ref's recount).

VMEM: the incidence is held whole, ``F·L·4`` bytes after padding, and the
kernel asks for ``VMEM_LIMIT`` bytes of scoped VMEM (within a v5e core's
128 MiB).  Incidences above ``MAX_INCIDENCE_BYTES`` are refused with a
``ValueError`` — 10240 flows × 256 links fits (10 MiB); the exact numpy
solver (``ops.maxmin_rates_arrays``) is the path for larger problems.

Static round count: each effective round saturates (and thereafter
silences) at least one link, so ``L`` rounds reach the fixed point and
the remaining iterations are identity (no flow is newly frozen once the
min share hits the BIG sentinel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.maxmin.ref import BIG, NOLINK_RATE

BL = 8               # link-axis pad multiple (float32 sublane tile)
CHUNK = 512          # flows per chunk (lane-tile multiple)
MAX_INCIDENCE_BYTES = 16 << 20
VMEM_LIMIT = 48 << 20


def _maxmin_kernel(inc_ref, cap_ref, rates_ref, active_ref, *, rounds: int):
    n = inc_ref.shape[0]
    col = (inc_ref.shape[1], 1)

    def count(c, users):
        return users + jnp.sum(inc_ref[c], axis=1, keepdims=True)

    users0 = jax.lax.fori_loop(0, n, count, jnp.zeros(col, jnp.float32))
    rates_ref[...] = jnp.zeros(rates_ref.shape, jnp.float32)
    active_ref[...] = jnp.ones(active_ref.shape, jnp.float32)

    def round_(_, carry):
        cap, users = carry                            # [L, 1] each
        share = jnp.where(users > 0, cap / jnp.maximum(users, 1.0), BIG)
        s = jnp.min(share)
        sat = ((share <= s) & (users > 0)).astype(jnp.float32)
        r = jnp.maximum(s, 0.0)

        def freeze(c, frozen):
            inc = inc_ref[c]                          # [L, CHUNK]
            active = active_ref[c]                    # [1, CHUNK]
            hit = jnp.sum(inc * sat, axis=0, keepdims=True) > 0
            newly = ((active > 0) & hit & (s < BIG)).astype(jnp.float32)
            rates_ref[c] = jnp.where(newly > 0, r, rates_ref[c])
            active_ref[c] = active * (1.0 - newly)
            return frozen + jnp.sum(inc * newly, axis=1, keepdims=True)

        frozen = jax.lax.fori_loop(0, n, freeze, jnp.zeros(col, jnp.float32))
        return cap - r * frozen, users - frozen

    jax.lax.fori_loop(0, rounds, round_, (cap_ref[...], users0))
    rates_ref[...] = jnp.where(active_ref[...] > 0, jnp.float32(NOLINK_RATE),
                               rates_ref[...])


@functools.partial(jax.jit, static_argnames=("rounds", "interpret"))
def _maxmin_padded(inc, cap, *, rounds: int, interpret: bool):
    """inc: [n_chunks, L, CHUNK] chunked links × flows incidence; cap:
    [L, 1].  Returns [n_chunks · CHUNK] rates."""
    n, L, C = inc.shape
    # whole-array dispatch: no grid — the incidence lives in VMEM
    out = pl.pallas_call(
        functools.partial(_maxmin_kernel, rounds=rounds),
        out_shape=jax.ShapeDtypeStruct((n, 1, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, 1, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(inc, cap)
    return out.reshape(n * C)


def maxmin_kernel(inc, cap, interpret: bool | None = None):
    """inc: [F, L] float 0/1 incidence; cap: [L] capacities.  Returns [F]
    float32 max-min rates, parity with ``ref.maxmin_ref``.  Padding is
    inert: padded links get cap 0 with no users (share = BIG sentinel,
    never the min while real work remains) and padded flows cross no link
    (they end active → NOLINK_RATE, sliced off).  Raises ``ValueError``
    when the padded incidence exceeds ``MAX_INCIDENCE_BYTES``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    F, L = inc.shape
    if L == 0:
        return jnp.full((F,), jnp.float32(NOLINK_RATE))
    lp = -(-L // BL) * BL
    fp = -(-max(F, 1) // 128) * 128
    chunk = min(CHUNK, fp)
    n = -(-fp // chunk)
    need = n * lp * chunk * 4
    if need > MAX_INCIDENCE_BYTES:
        raise ValueError(
            f"maxmin kernel: {F} flows × {L} links pads to a "
            f"{need / 2**20:.1f} MiB incidence, over the kernel's "
            f"{MAX_INCIDENCE_BYTES >> 20} MiB VMEM budget — use the exact "
            f"solver (maxmin_rates_arrays) or impl='ref' for this size")
    inc = jnp.asarray(inc, jnp.float32)
    cap = jnp.asarray(cap, jnp.float32)
    incp = jnp.pad(inc, ((0, n * chunk - F), (0, lp - L)))
    inc3 = incp.reshape(n, chunk, lp).transpose(0, 2, 1)
    capp = jnp.pad(cap, (0, lp - L))[:, None]
    return _maxmin_padded(inc3, capp, rounds=max(L, 1),
                          interpret=interpret)[:F]
