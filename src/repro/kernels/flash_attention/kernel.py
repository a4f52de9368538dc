"""Blockwise online-softmax (flash) attention as a Pallas TPU kernel.

Grid: (batch·q_heads, S/BQ, S/BK) with the KV dimension innermost — the TPU
grid is sequential, so the (m, l, acc) running-softmax state lives in VMEM
scratch across KV steps and is finalised on the last one.  GQA is an
index_map: the KV block for flattened q-head ``bh`` comes from kv head
``(bh % Hq) // (Hq // Hk)``.  Causal/sliding-window masking is computed from
block offsets; fully-masked KV blocks still iterate (the grid is static) but
their contribution is exp(-inf)=0 — the skip optimisation is recorded as a
perf-iteration idea in EXPERIMENTS.md §Perf.

VMEM per step (f32): q/k/v/acc tiles 4·BQ·D ≈ 4·128·128·4B = 256 KiB for
D=128 — MXU-aligned (BQ, BK, D all multiples of 128 when D permits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128
BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, window: int | None,
                  n_kv: int, s_valid: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # [BQ, D]
    k = k_ref[0]                       # [BK, D]
    v = v_ref[0]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [BQ, BK]

    qpos = iq * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    kpos = ik * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    mask = kpos < s_valid            # padded KV columns contribute nothing
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, logits.max(axis=1))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(logits - m_cur[:, None])
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ik == n_kv - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "interpret", "s_valid"))
def flash_attention_padded(q, k, v, *, causal: bool, window: int | None,
                           scale: float, s_valid: int, interpret: bool):
    """q: [BH, S, D] flattened (batch·q_heads); k/v: [BHk, S, D] flattened
    (batch·kv_heads); requires S % BQ == 0 == S % BK and knowledge of the
    head grouping encoded by the caller in the index mapping."""
    BHq, S, D = q.shape
    BHk = k.shape[0]
    group = BHq // BHk
    n_kv = S // BK
    grid = (BHq, S // BQ, n_kv)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, n_kv=n_kv, s_valid=s_valid)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, BK, D), lambda b, iq, ik: (b // group, ik, 0)),
            pl.BlockSpec((1, BK, D), lambda b, iq, ik: (b // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, D), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BHq, S, D), q.dtype),
        scratch_shapes=[
            pltpu_scratch((BQ,), jnp.float32),
            pltpu_scratch((BQ,), jnp.float32),
            pltpu_scratch((BQ, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def pltpu_scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
