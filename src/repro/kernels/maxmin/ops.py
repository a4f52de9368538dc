"""Shape-stable array API for the max-min water-filling solver.

Three interchangeable implementations sit behind one CSR flow-path layout
(``path_links`` + ``path_off``, the struct-of-arrays form every fast lane
shares — see ``repro.net.soa``):

* :func:`maxmin_rates_arrays` — the **default**: an exact array
  re-implementation of the historical dict/set progressive water-filling
  loop (kept as ``repro.net.flows.maxmin_rates_dict`` for parity tests).
  Bit-for-bit equal outputs, which is what keeps ``fidelity="packet"``
  hybrid runs and every CI counter identical across the refactor: link
  capacities are seeded in first-appearance order, the most-contended link
  is chosen by ``argmin`` (first occurrence == the dict loop's strict ``<``
  over insertion order), and every per-round capacity decrement subtracts
  the *identical* fair-share scalar, so accumulation order cannot change a
  single bit.
* :func:`maxmin_rates_jax` with ``impl="ref"`` — the pure-JAX fixed-point
  oracle (``repro.kernels.maxmin.ref``), dense flow×link incidence.
* the Pallas kernel (``repro.kernels.maxmin.kernel``), same fixed-point
  algorithm in VMEM — selected with ``impl="kernel"``.

jax is imported lazily: the packet path (including the sharded loop's
spawn workers) stays jax-free unless a jax implementation is requested.
"""
from __future__ import annotations

from collections.abc import Mapping, Sequence

import numpy as np

# deterministic instrumentation for the CI counter gate: every solver
# invocation (any impl) bumps these; benchmarks/ci_regression.py snapshots
# them around its scenario pass
SOLVER_COUNTERS = {"invocations": 0, "max_flows": 0}


def reset_counters() -> dict:
    """Zero the module counters and return the values they held."""
    held = dict(SOLVER_COUNTERS)
    SOLVER_COUNTERS["invocations"] = 0
    SOLVER_COUNTERS["max_flows"] = 0
    return held


def paths_to_arrays(paths: Mapping[int, Sequence[int]]):
    """CSR layout of a ``{fid: [port ids]}`` mapping, preserving the
    mapping's iteration order (the order seeds link first-appearance order,
    which the exact solver's tie-breaks depend on)."""
    fids = list(paths)
    off = np.zeros(len(fids) + 1, dtype=np.int64)
    chunks = []
    for i, fid in enumerate(fids):
        p = paths[fid]
        off[i + 1] = off[i] + len(p)
        if len(p):
            chunks.append(np.asarray(p, dtype=np.int64))
    links = (np.concatenate(chunks) if chunks
             else np.zeros(0, dtype=np.int64))
    return fids, links, off


def _capacities(link_bw, links: np.ndarray) -> np.ndarray:
    """Gather ``link_bw[l]`` for dense link ids — ``link_bw`` is anything
    indexable by port id (ndarray, list, or dict)."""
    if isinstance(link_bw, np.ndarray):
        return link_bw[links].astype(np.float64)
    return np.array([float(link_bw[int(l)]) for l in links], dtype=np.float64)


def _gather_csr(off: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Concatenated entry indices of CSR ``rows`` (vectorized range-concat)."""
    starts = off[rows]
    lens = (off[rows + 1] - starts).astype(np.int64)
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    out = np.repeat(starts - (np.cumsum(lens) - lens), lens)
    return out + np.arange(total, dtype=np.int64)


def maxmin_rates_arrays(path_links: np.ndarray, path_off: np.ndarray,
                        link_bw) -> np.ndarray:
    """Exact progressive water-filling over CSR paths: float64 rates
    (bytes/s) per flow, bit-identical to the historical dict solver.

    ``path_links``: concatenated port ids; ``path_off``: per-flow offsets
    (len F+1); ``link_bw``: capacities indexable by port id.
    """
    F = len(path_off) - 1
    SOLVER_COUNTERS["invocations"] += 1
    if F > SOLVER_COUNTERS["max_flows"]:
        SOLVER_COUNTERS["max_flows"] = F
    rates = np.zeros(F, dtype=np.float64)
    if F == 0:
        return rates
    E = int(path_off[-1])
    if E == 0:                      # no flow crosses a link
        rates[:] = 1e12
        return rates
    path_links = np.asarray(path_links, dtype=np.int64)
    path_off = np.asarray(path_off, dtype=np.int64)
    # dense link ids in first-appearance order (== dict insertion order)
    uniq, first, inv = np.unique(path_links, return_index=True,
                                 return_inverse=True)
    order = np.argsort(first, kind="stable")
    rank = np.empty(len(uniq), dtype=np.int64)
    rank[order] = np.arange(len(uniq), dtype=np.int64)
    dense = rank[inv]               # per path entry: dense link index
    L = len(uniq)
    cap = _capacities(link_bw, uniq[order])
    flow_of_entry = np.repeat(np.arange(F, dtype=np.int64),
                              np.diff(path_off))
    # link -> entries CSR (which flows cross each link)
    by_link = np.argsort(dense, kind="stable")
    link_off = np.searchsorted(dense[by_link], np.arange(L + 1))
    # per-flow *unique* links (the dict kept a set per link, so a repeated
    # link in one path counts one user — but its capacity is decremented
    # once per occurrence, which the raw-entry subtraction below preserves)
    pair = flow_of_entry * L + dense
    upair = np.unique(pair)
    u_link = (upair % L).astype(np.int64)
    u_flow = (upair // L).astype(np.int64)
    u_off = np.searchsorted(u_flow, np.arange(F + 1))
    users = np.bincount(u_link, minlength=L).astype(np.int64)

    unfrozen = np.ones(F, dtype=bool)
    n_left = F
    while n_left:
        with np.errstate(divide="ignore", invalid="ignore"):
            share = np.where(users > 0, cap / users, np.inf)
        best = int(np.argmin(share))
        if users[best] <= 0:        # only link-less flows remain
            rates[unfrozen] = 1e12
            break
        s = share[best]
        if s < 0.0:
            s = 0.0
        sel = flow_of_entry[by_link[link_off[best]:link_off[best + 1]]]
        sel = np.unique(sel)
        sel = sel[unfrozen[sel]]
        rates[sel] = s
        unfrozen[sel] = False
        n_left -= len(sel)
        # every decrement this round subtracts the identical scalar ``s``
        # (or integer 1), so the order of repeated updates cannot change
        # the result — np.subtract.at is bit-equal to the dict loop
        np.subtract.at(cap, dense[_gather_csr(path_off, sel)], s)
        np.subtract.at(users, u_link[_gather_csr(u_off, sel)], 1)
    return rates


def solve_paths(paths: Mapping[int, Sequence[int]], link_bw) -> dict[int, float]:
    """Dict-in/dict-out convenience over :func:`maxmin_rates_arrays` —
    the drop-in body of ``repro.net.flows.maxmin_rates``."""
    fids, links, off = paths_to_arrays(paths)
    rates = maxmin_rates_arrays(links, off, link_bw)
    return dict(zip(fids, rates.tolist()))


# ---------------------------------------------------------------------- #
# jax implementations (dense incidence; lazy import)
# ---------------------------------------------------------------------- #
def incidence_from_csr(path_links: np.ndarray, path_off: np.ndarray,
                       link_bw) -> tuple[np.ndarray, np.ndarray]:
    """Dense ``(incidence [F, L], cap [L])`` float32 arrays over the links
    that actually appear, in first-appearance order — the fixed-shape input
    of the jax/Pallas implementations."""
    F = len(path_off) - 1
    path_links = np.asarray(path_links, dtype=np.int64)
    if len(path_links) == 0:
        return np.zeros((F, 0), np.float32), np.zeros(0, np.float32)
    uniq, first, inv = np.unique(path_links, return_index=True,
                                 return_inverse=True)
    order = np.argsort(first, kind="stable")
    rank = np.empty(len(uniq), dtype=np.int64)
    rank[order] = np.arange(len(uniq), dtype=np.int64)
    dense = rank[inv]
    L = len(uniq)
    inc = np.zeros((F, L), dtype=np.float32)
    flow_of_entry = np.repeat(np.arange(F, dtype=np.int64),
                              np.diff(np.asarray(path_off, dtype=np.int64)))
    inc[flow_of_entry, dense] = 1.0
    cap = _capacities(link_bw, uniq[order]).astype(np.float32)
    return inc, cap


def maxmin_rates_jax(path_links, path_off, link_bw, *, impl: str = "ref",
                     interpret: bool | None = None) -> np.ndarray:
    """Fixed-point max-min via the jax ref (``impl="ref"``) or the Pallas
    kernel (``impl="kernel"``).  float32 — approximate parity with the
    exact solver (≲1e-4 rel), exact parity kernel↔ref (on CPU in
    interpret mode and on a TPU v5e).  The kernel raises ``ValueError``
    for an incidence over its VMEM budget."""
    SOLVER_COUNTERS["invocations"] += 1
    F = len(path_off) - 1
    if F > SOLVER_COUNTERS["max_flows"]:
        SOLVER_COUNTERS["max_flows"] = F
    inc, cap = incidence_from_csr(path_links, path_off, link_bw)
    if impl == "ref":
        from repro.kernels.maxmin.ref import maxmin_ref
        return np.asarray(maxmin_ref(inc, cap))
    if impl == "kernel":
        from repro.kernels.maxmin.kernel import maxmin_kernel
        return np.asarray(maxmin_kernel(inc, cap, interpret=interpret))
    raise ValueError(f"unknown impl {impl!r} (use 'ref' or 'kernel')")
