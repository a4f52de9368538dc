"""The numbers ``correct`` is decided by, and the judgement against limits.

Per-flow error is ``|fct - ref| / ref`` over the reference's flows, as the
program's ``summarize_pair`` computes it.  Unlike that arithmetic, a flow
missing from the answer (or one the reference does not have) is counted in
``flows_missing`` instead of being left out of the mean.
"""
from __future__ import annotations

import math

import numpy as np


def numbers(fct: dict, iteration: float | None, ref_fct: dict,
            ref_iteration: float) -> dict:
    missing = (sum(1 for f in ref_fct if f not in fct)
               + sum(1 for f in fct if f not in ref_fct))
    errs = np.array([abs(fct[f] - r) / r for f, r in ref_fct.items()
                     if r > 0 and f in fct], np.float64)
    return {
        "flows_missing": float(missing),
        "fct_err_mean": float(errs.mean()) if errs.size else math.inf,
        "fct_err_max": float(errs.max()) if errs.size else math.inf,
        "iter_err": (abs(iteration - ref_iteration) / ref_iteration
                     if iteration is not None else math.inf),
    }


def worst(rows: list[dict]) -> dict:
    """Per number, the worst reading over ``rows`` (NaN if any is NaN)."""
    out = {}
    for k in rows[0]:
        vals = [r[k] for r in rows]
        out[k] = math.nan if any(math.isnan(v) for v in vals) else max(vals)
    return out


def within(value: float, limit: float) -> bool:
    return value <= limit                 # False for NaN


def judge(compared: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: every number the limits name, beside its
    limit.  A limit file that names no number decides nothing and fails."""
    checks = {k: {"value": compared[k], "limit": float(v["limit"])}
              for k, v in limits.items()}
    ok = bool(checks) and all(within(c["value"], c["limit"])
                              for c in checks.values())
    return ok, checks
