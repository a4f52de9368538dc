"""Pure-jnp oracle for fixed-point max-min water-filling.

Dense ``incidence [F, L]`` / ``cap [L]`` form (see
``ops.incidence_from_csr``).  Each round saturates the most-contended
link — smallest ``cap/users`` fair share — and freezes every flow that
crosses it; ties freeze together, which converges to the same allocation
as the one-link-at-a-time progressive loop because a link tied at share
``s`` still has share exactly ``s`` after the other tied links' users are
subtracted.  At most one round per link does work, so ``L`` static rounds
reach the fixed point and further rounds are identity.

Scope: paths must be *simple* (no repeated link within one flow's path —
true of every real route).  The historical dict solver decrements a
link's capacity once per *occurrence* while counting one user per flow;
0/1 incidence cannot express that quirk, so only the exact array solver
(``ops.maxmin_rates_arrays``) reproduces it bit-for-bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import compile_cache

compile_cache.enable()

BIG = 3e38                   # sentinel share for user-less links
NOLINK_RATE = 1e12           # rate for flows that cross no link (dict parity)


def maxmin_ref(inc, cap):
    """inc: [F, L] float 0/1 flow-over-link incidence; cap: [L] float
    capacities (bytes/s).  Returns [F] float32 max-min fair rates."""
    inc = jnp.asarray(inc, jnp.float32)
    cap = jnp.asarray(cap, jnp.float32)
    F, L = inc.shape
    if L == 0:
        return jnp.full((F,), NOLINK_RATE, jnp.float32)

    def round_(_, carry):
        rates, cap, active = carry
        users = jnp.sum(inc * active[:, None], axis=0)
        share = jnp.where(users > 0, cap / jnp.maximum(users, 1.0), BIG)
        s = jnp.min(share)
        sat = ((share <= s) & (users > 0)).astype(jnp.float32)
        hit = jnp.sum(inc * sat[None, :], axis=1) > 0
        newly = (active > 0) & hit & (s < BIG)
        r = jnp.maximum(s, 0.0)
        rates = jnp.where(newly, r, rates)
        newly_f = newly.astype(jnp.float32)
        cap = cap - r * jnp.sum(inc * newly_f[:, None], axis=0)
        return rates, cap, active * (1.0 - newly_f)

    rates, _, active = jax.lax.fori_loop(
        0, max(L, 1), round_,
        (jnp.zeros(F, jnp.float32), cap, jnp.ones(F, jnp.float32)))
    return jnp.where(active > 0, jnp.float32(NOLINK_RATE), rates)
