"""The deployment a configuration file describes, built without the program.

A configuration (``bench/configs/<name>.json``) states a training
deployment: the model's sizes, its parallel layout, the compute model, the
fabric and the packet-level knobs.  This module turns it into what both
references consume:

* :class:`Fabric` — the rail-optimized fat-tree as directed ports, with
  deterministic ECMP routing (shortest path, ties broken by a hash of flow
  id, node and hop);
* :func:`training_phases` — one training iteration as a DAG of phases, each
  "compute for ``compute`` seconds, then launch these flows".  It is built
  in two steps: a *stage rule* says what each pipeline stage costs, and
  :func:`gpipe_phases` lays the stages out as GPipe forward and backward
  over the pipeline with point-to-point activations, expert all-to-all
  inside each forward/backward phase of an MoE model, and bidirectional
  ring all-reduces of each stage's gradients.

Flow ids are allocated in generation order, and every float is computed in
the order the published formulas are written, so a run of the program on
the same deployment can be compared flow by flow.

Stage rules
-----------
A stage rule is a function ``stage_loads(cfg) -> list[StageLoad]``: one
:class:`StageLoad` per pipeline stage, stage 0 first, ``cfg["parallel"]
["pp"]`` of them.  Its one input is the configuration file as parsed JSON;
it reads nothing else and imports nothing of the program.  The Table-1
rule, :func:`stage_loads` below, makes every stage equal and applies where
the configuration names no rule.  A configuration names another with
``"stage_rule": "<name>"``: the module ``bench/reference/<name>.py``, which
exposes ``stage_loads``.

The fields of a :class:`StageLoad` are per rank of the stage and already
multiplied by the configuration's ``flow_scale``, as the simulator runs
them:

* ``t_fwd``, ``t_bwd`` — seconds of compute of one micro-batch's forward
  and backward, before stragglers stretch them;
* ``a2a_bytes`` — bytes a rank sends in the expert all-to-alls of each
  forward and each backward phase, dispatch and combine together; each
  other rank of its EP domain (``n`` ranks) gets ``a2a_bytes / n``;
* ``act_bytes`` — bytes of activations a rank sends to its peer in the
  next stage for each micro-batch; the backward sends as many back.  The
  last stage's is not used;
* ``dense_grad_bytes`` — bytes a rank all-reduces over its DP ring;
* ``expert_grad_bytes`` — bytes a rank all-reduces over its expert-DP
  group: the ranks at the same position in the EP domains carved from its
  DP group.  No ring where it is 0 or the group has one rank.

The builder owns the layout: rank ``t + tp*(d + dp*s)``, the DP groups, EP
domains of ``ep_domain`` consecutive ranks of a DP group, the micro-batch
count, the stragglers and the flow ids.
"""
from __future__ import annotations

import dataclasses

import numpy as np

UNREACHED = np.iinfo(np.int32).max


@dataclasses.dataclass
class Flow:
    fid: int
    src: int
    dst: int
    size: float
    tag: str


@dataclasses.dataclass
class Phase:
    name: str
    flows: list[Flow]
    deps: list[int]
    compute: float


class Fabric:
    """Directed ports of a rail-optimized fat-tree: GPU ``r`` of each
    server on a rail-``r`` leaf, every leaf on every spine through a trunk
    of ``spine_trunk`` times the host link rate.  Each GPU is one host."""

    def __init__(self, n_servers: int, gpus_per_server: int, leaf_radix: int,
                 n_spines: int, bw: float, delay: float,
                 spine_trunk: float) -> None:
        self.n_hosts = n_servers * gpus_per_server
        leaves_per_rail = max(1, -(-n_servers // leaf_radix))
        leaf0 = self.n_hosts
        spine0 = leaf0 + gpus_per_server * leaves_per_rail
        self.n_nodes = spine0 + n_spines
        cables = []
        for s in range(n_servers):
            for r in range(gpus_per_server):
                cables.append((s * gpus_per_server + r,
                               leaf0 + r * leaves_per_rail + s // leaf_radix,
                               bw))
        for leaf in range(leaf0, spine0):
            for sp in range(n_spines):
                cables.append((leaf, spine0 + sp, bw * spine_trunk))
        src, dst, rate = [], [], []
        for a, b, c in cables:                # two directed ports a cable
            src += [a, b]
            dst += [b, a]
            rate += [c, c]
        self.bw = np.asarray(rate, np.float64)
        self.delay = np.full(len(src), delay, np.float64)
        self.adj: list[list[tuple[int, int]]] = [[] for _ in range(self.n_nodes)]
        for port, (a, b) in enumerate(zip(src, dst)):
            self.adj[a].append((port, b))
        self._dist: dict[int, list[int]] = {}

    @classmethod
    def from_config(cls, cfg: dict) -> "Fabric":
        f = cfg["fabric"]
        return cls(f["n_servers"], f["gpus_per_server"], f["leaf_radix"],
                   f["n_spines"], f["bw"], f["delay"], f["spine_trunk"])

    def _hops_to(self, dst: int) -> list[int]:
        if dst not in self._dist:
            dist = [UNREACHED] * self.n_nodes
            dist[dst] = 0
            frontier = [dst]
            while frontier:
                nxt = []
                for u in frontier:
                    for _, v in self.adj[u]:
                        if dist[v] > dist[u] + 1:
                            dist[v] = dist[u] + 1
                            nxt.append(v)
                frontier = nxt
            self._dist[dst] = dist
        return self._dist[dst]

    def route(self, src: int, dst: int, fid: int) -> list[int]:
        """Ports from ``src`` to ``dst``: a shortest path whose hop ``k``
        out of node ``n`` takes candidate ``(fid*1000003 + n*10007 +
        k*101) mod #candidates`` among the ports one hop closer."""
        dist = self._hops_to(dst)
        path, node, hop = [], src, 0
        while node != dst:
            cands = [(p, v) for p, v in self.adj[node]
                     if dist[v] == dist[node] - 1]
            port, node = cands[(fid * 1000003 + node * 10007 + hop * 101)
                               % len(cands)]
            path.append(port)
            hop += 1
        return path


def straggler_ranks(seed: int, count: int, n_ranks: int) -> list[int]:
    """``count`` distinct ranks drawn from ``seed``: the variant a run
    simulates."""
    rng = np.random.default_rng(seed)
    return sorted(int(r) for r in rng.choice(n_ranks, size=count,
                                             replace=False))


@dataclasses.dataclass(frozen=True)
class StageLoad:
    """What one pipeline stage costs a rank (module docstring)."""
    t_fwd: float
    t_bwd: float
    a2a_bytes: float
    act_bytes: float
    dense_grad_bytes: float
    expert_grad_bytes: float


def stage_loads(cfg: dict) -> list[StageLoad]:
    """The Table-1 rule: the model split evenly over the stages, its
    parameters and active parameters each ``1/pp``, every gradient on the
    DP ring."""
    m, par, cmp = cfg["model"], cfg["parallel"], cfg["compute"]
    scale = cfg["flow_scale"]
    tp, pp = par["tp"], par["pp"]
    tokens_mb = m["micro_batch"] * m["seq_len"]
    stage_layers = max(1, m["n_layers"] // pp)
    stage_params = m["params"] / pp
    stage_active = m["active_params"] / pp
    t_fwd = (2 * stage_active * tokens_mb
             / (cmp["chip_flops"] * cmp["mfu"] * tp) * scale)
    act_bytes = (m["micro_batch"] * m["seq_len"] * m["d_model"]
                 * m["dtype_bytes"] / tp * scale)
    grad_bytes = stage_params / tp * m["dtype_bytes"] * scale
    a2a_bytes = 0.0
    if m["moe_experts"]:
        moe_layers = max(1, stage_layers // m["moe_layer_every"])
        a2a_bytes = 2 * (tokens_mb * m["d_model"] * m["dtype_bytes"]
                         * max(m["moe_top_k"], 1) * moe_layers / tp * scale)
    return [StageLoad(t_fwd, 2 * t_fwd, a2a_bytes, act_bytes, grad_bytes,
                      0.0)] * pp


def training_phases(cfg: dict, slow: dict[int, float]) -> list[Phase]:
    """One training iteration of the configured deployment by the Table-1
    rule; ``slow`` maps a rank to the factor its compute is stretched by."""
    return gpipe_phases(cfg, stage_loads(cfg), slow)


def gpipe_phases(cfg: dict, loads: list[StageLoad],
                 slow: dict[int, float]) -> list[Phase]:
    """One GPipe training iteration whose stages cost ``loads``; ``slow``
    maps a rank to the factor its compute is stretched by."""
    par = cfg["parallel"]
    tp, dp, pp = par["tp"], par["dp"], par["pp"]
    if len(loads) != pp:
        raise ValueError(f"{len(loads)} stage loads for {pp} pipeline "
                         f"stages")
    per_stage = tp * dp                       # ranks of one pipeline stage

    def rank(t: int, d: int, s: int) -> int:
        return t + tp * (d + dp * s)

    dp_groups = [[rank(t, d, s) for d in range(dp)]
                 for s in range(pp) for t in range(tp)] if dp > 1 else []
    # EP domains: consecutive ranks of a DP group; expert-DP groups: the
    # ranks at one position across the domains of a DP group
    width = max(par["ep_domain"], 1)
    domains = [[g[i:i + width] for i in range(0, len(g), width)]
               for g in dp_groups]
    ep_groups = [c for cs in domains for c in cs if len(c) > 1]
    edp_groups = [g for cs in domains for j in range(width)
                  if len(g := [c[j] for c in cs if j < len(c)]) > 1]
    pp_pairs = [[(rank(t, d, s), rank(t, d, s + 1))
                 for d in range(dp) for t in range(tp)]
                for s in range(pp - 1)]
    M = par["num_microbatches"] or pp

    def stretched(s: int, t: float) -> float:
        for r in sorted(slow):
            if r // per_stage == s:
                t = t * slow[r]
        return t

    next_fid = [0]

    def flow(src: int, dst: int, size: float, tag: str) -> Flow:
        next_fid[0] += 1
        return Flow(next_fid[0] - 1, src, dst, size, tag)

    def all_to_all(s: int, tag: str) -> list[Flow]:
        flows = []
        for g in ep_groups:
            if g[0] // per_stage == s:
                per = loads[s].a2a_bytes / len(g)
                flows += [flow(a, b, per, tag) for a in g for b in g
                          if a != b]
        return flows

    def ring(groups: list[list[int]], s: int, nbytes: float,
             tag: str) -> list[Flow]:
        flows = []
        for g in groups:
            if g[0] // per_stage != s:
                continue
            n = len(g)
            per = 2 * (n - 1) / n * nbytes
            per /= 2                          # both ring directions
            for i, a in enumerate(g):
                flows.append(flow(a, g[(i + 1) % n], per, tag))
                flows.append(flow(a, g[(i - 1) % n], per, tag))
        return flows

    phases: list[Phase] = []
    idx: dict[tuple, int] = {}

    def add(name, flows, deps, compute) -> int:
        phases.append(Phase(name, flows, deps, compute))
        return len(phases) - 1

    for mb in range(M):
        for s in range(pp):
            deps = ([idx["f", mb, s - 1]] if s > 0 else []) + \
                   ([idx["f", mb - 1, s]] if mb > 0 else [])
            flows = []
            if loads[s].a2a_bytes:
                flows += all_to_all(s, f"ep.fwd.m{mb}.s{s}")
            if s < pp - 1:
                flows += [flow(a, b, loads[s].act_bytes, f"pp.fwd.m{mb}.s{s}")
                          for a, b in pp_pairs[s]]
            idx["f", mb, s] = add(f"fwd.m{mb}.s{s}", flows, deps,
                                  stretched(s, loads[s].t_fwd))
    for mb in range(M):
        for s in reversed(range(pp)):
            deps = [idx["f", M - 1, pp - 1]]
            if s < pp - 1:
                deps.append(idx["b", mb, s + 1])
            if mb > 0:
                deps.append(idx["b", mb - 1, s])
            flows = []
            if loads[s].a2a_bytes:
                flows += all_to_all(s, f"ep.bwd.m{mb}.s{s}")
            if s > 0:
                flows += [flow(b, a, loads[s - 1].act_bytes,
                               f"pp.bwd.m{mb}.s{s}")
                          for a, b in pp_pairs[s - 1]]
            idx["b", mb, s] = add(f"bwd.m{mb}.s{s}", flows, deps,
                                  stretched(s, loads[s].t_bwd))
    for s in range(pp):
        deps = [idx["b", mb, s] for mb in range(M)]
        flows = ring(dp_groups, s, loads[s].dense_grad_bytes, f"dp.s{s}")
        if flows:
            add(f"dp.s{s}", flows, deps, 0.0)
        if loads[s].expert_grad_bytes:
            flows = ring(edp_groups, s, loads[s].expert_grad_bytes,
                         f"edp.s{s}")
            if flows:
                add(f"edp.s{s}", flows, deps, 0.0)
    return phases
