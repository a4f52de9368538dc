"""Engine protocol + registry: interchangeable simulation backends.

Every backend consumes the same declarative :class:`Scenario` and returns
the same structured :class:`RunResult`, so fidelity is a one-word knob:

    packet    per-packet DES oracle (the ns-3 stand-in)
    wormhole  the same oracle under the memoizing/fast-forwarding kernel
    hybrid    adaptive per-partition packet/flow granularity (bounded error
              on *unsteady* traffic — the accuracy/speed axis)
    fluid     vectorized JAX rate dynamics (vmappable for batched sweeps)
    analytic  flow-level max-min fair sharing (cheapest, coarsest)
    learned   MLP fitted on campaign RunStores (``repro.learned``) — batched
              what-if queries at thousands of scenarios/sec, in-distribution

Third-party backends register with ``@register_engine("name")``.

Each built-in ``run`` records three spans (``repro.spans``):
``repro.api.build`` from entry to its timed loop, ``repro.engine.loop``
over exactly the interval ``RunResult.wall_time`` measures, and
``repro.api.collect`` around the result's assembly.
"""
from __future__ import annotations

import dataclasses
import time

from repro.api.analytic import AnalyticSim
from repro.api.results import RunResult
from repro.api.scenario import Scenario
from repro.core.memo import SimDB
from repro.core.wormhole import WormholeConfig, WormholeKernel
from repro.net import chaos as chaos_mod
from repro.net.hybrid_sim import FIDELITIES, HybridConfig, HybridKernel, HybridSim
from repro.net.packet_sim import PacketSim
from repro.net.sharded_sim import ShardedPacketSim
from repro.spans import span
from repro.workload.driver import WorkloadDriver

# repro.net.fluid_jax (and with it jax) is imported lazily by FluidEngine:
# packet/wormhole runs — including run_many worker processes — must not pay
# the jax import or spin up its thread pools.

_REGISTRY: dict[str, type] = {}


def register_engine(name: str):
    """Class decorator: make ``name`` resolvable through ``get_engine``."""
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_engine(name: str) -> "Engine":
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; "
            f"available: {', '.join(available_backends())}") from None
    return cls()


class Engine:
    """Backend protocol: evaluate scenarios into :class:`RunResult`s.

    ``uses_db = True`` declares that ``run`` accepts a ``db=`` SimDB —
    the seam campaigns use to thread their memo DB through a backend
    without hard-coding backend names.

    ``on_device = True`` declares that ``run`` puts work on the JAX
    accelerator.  One process holds an accelerator, so campaigns refuse to
    fan such a backend out over spawn workers.

    ``option_names`` declares the opts ``run`` accepts; the API layer
    (``Campaign.submit``/``sweep``/``compare`` and the CLI) rejects
    anything else through :meth:`check_opts` with one shared error naming
    the accepted set — so a typoed opt fails loudly instead of keying a
    phantom experiment or being silently swallowed by ``**opts``.  Leave
    it None (the default) to opt out of validation (third-party engines
    that have not declared their opts keep working unchecked)."""
    name = "abstract"
    uses_db = False
    on_device = False
    option_names: tuple[str, ...] | None = None

    def check_opts(self, opts: dict) -> None:
        """Raise ValueError on any opt this backend does not accept."""
        if self.option_names is None:
            return
        unknown = sorted(set(opts) - set(self.option_names))
        if unknown:
            accepted = ", ".join(sorted(self.option_names)) or "(none)"
            raise ValueError(
                f"backend {self.name!r} does not accept "
                f"opt{'s' if len(unknown) > 1 else ''} "
                f"{', '.join(map(repr, unknown))}; accepted opts: "
                f"{accepted}")

    def run(self, scenario: Scenario, **opts) -> RunResult:
        raise NotImplementedError

    def run_batch(self, scenarios: list[Scenario], **opts) -> list[RunResult]:
        return [self.run(s, **opts) for s in scenarios]


# ---------------------------------------------------------------------- #
# packet-level backends (event simulators driven by the workload layer)
# ---------------------------------------------------------------------- #
def _drive(scenario: Scenario, sim) -> "WorkloadDriver | None":
    if scenario.kind == "workload":
        return WorkloadDriver(sim, scenario.build_phases())
    for fl in scenario.flows:
        sim.add_flow(dataclasses.replace(fl))
    plan = chaos_mod.plan_for(scenario)
    if plan is not None:
        # flow scenarios skip the phase DAG, so the phase-level mice
        # injectors land here: each arrival is a plain flow whose start
        # carries the phase's compute (= the Poisson arrival time)
        for ph in plan.mice_phases(scenario._n_hosts()):
            sim.add_flow(dataclasses.replace(ph.flows[0], start=ph.compute))
    return None


def _collect(backend: str, scenario: Scenario, sim, driver, wall: float,
             kernel_report: dict | None = None,
             record_rtt=()) -> RunResult:
    with span("repro.api.collect"):
        if driver is not None:
            assert driver.finished, f"{scenario.name}: program did not finish"
            iteration = driver.iteration_time
        elif sim.results:
            iteration = (max(r.finish for r in sim.results.values())
                         - min(r.start for r in sim.results.values()))
        else:
            iteration = None
        extras = {}
        if record_rtt:
            extras["rtt_samples"] = {fid: list(sim.flows[fid].rtt_samples)
                                     for fid in record_rtt}
        return RunResult(
            backend=backend, scenario=scenario.name,
            fcts={fid: r.fct for fid, r in sim.results.items()},
            flow_bytes={fid: r.bytes for fid, r in sim.results.items()},
            tags={fid: r.tag for fid, r in sim.results.items()},
            iteration_time=iteration, events_processed=sim.events_processed,
            wall_time=wall, kernel_report=kernel_report, extras=extras)


@register_engine("packet")
class PacketEngine(Engine):
    """Baseline per-packet DES — the accuracy oracle everything else is
    judged against.

    opts (shared by the wormhole subclass):
      parallel       None (single-heap serial loop) or ``"partitions"``
                     (partition-sharded loop, ``repro.net.sharded_sim``)
      intra_workers  worker processes for the sharded loop's heavy-lane
                     fan-out; 1 keeps sharded execution in-process.  Results
                     are identical to the serial loop for any value.
    """
    option_names = ("intra_workers", "parallel", "record_rtt", "until",
                    "validate")

    def _make_kernel(self, scenario: Scenario, **opts):
        return None, None

    def run(self, scenario: Scenario, record_rtt=(), until: float = float("inf"),
            parallel: str | None = None, intra_workers: int = 1,
            validate: bool = False, **opts) -> RunResult:
        with span("repro.api.build"):
            plan = chaos_mod.plan_for(scenario)
            chaos_mod.check_backend(plan, self.name, intra_workers=intra_workers)
            topo = scenario.build_topology()
            kernel, report_fn = self._make_kernel(scenario, **opts)
            if parallel is None or parallel == "none":
                if intra_workers > 1 or validate:
                    # silently running the serial loop would make the user
                    # believe the fan-out (or invariant checking) was active
                    raise ValueError(
                        "intra_workers/validate require parallel='partitions'")
                sim = PacketSim(topo, kernel=kernel, **scenario.sim)
            elif parallel == "partitions":
                sim = ShardedPacketSim(topo, kernel=kernel,
                                       intra_workers=intra_workers,
                                       validate=validate, **scenario.sim)
            else:
                raise ValueError(
                    f"unknown parallel mode {parallel!r} (use 'partitions')")
            sim.record_rtt_fids = set(record_rtt)
            driver = _drive(scenario, sim)
            if plan is not None and plan.has_link_events:
                plan.install(sim)
        with span("repro.engine.loop"):
            t0 = time.perf_counter()
            try:
                sim.run(until=until)
            finally:
                if parallel == "partitions":
                    sim.close()
            wall = time.perf_counter() - t0
        result = _collect(self.name, scenario, sim, driver, wall,
                          kernel_report=report_fn() if report_fn else None,
                          record_rtt=record_rtt)
        if parallel == "partitions":
            result.extras["shard"] = sim.shard_report()
        return result


@register_engine("wormhole")
class WormholeEngine(PacketEngine):
    """Packet oracle + the Wormhole memoization/fast-forwarding kernel.

    opts:
      config   WormholeConfig or dict merged over scenario.kernel
      db       a SimDB to reuse across runs (cross-run warm cache, §6.1);
               per-run hit/lookup deltas land in kernel_report["run_db_*"].
               For a *durable* DB, open a campaign — ``Campaign.open(dir)``
               persists ``simdb.json`` automatically and ``python -m repro
               serve`` shares it across hosts — or manage an explicit
               ``SimDB.load_or_new``/``save`` pair yourself.
    """
    uses_db = True
    option_names = PacketEngine.option_names + ("config", "db")

    def run(self, scenario: Scenario, db: SimDB | None = None,
            **opts) -> RunResult:
        return super().run(scenario, db=db, **opts)

    def _make_kernel(self, scenario: Scenario, config=None, db: SimDB | None = None,
                     **opts):
        if isinstance(config, WormholeConfig):
            cfg = config
        else:
            cfg = WormholeConfig(**{**scenario.kernel, **(config or {})})
        kernel = WormholeKernel(cfg, db=db)
        hits0, lookups0 = kernel.db.hits, kernel.db.lookups

        def report():
            rep = kernel.report()
            rep["run_db_hits"] = kernel.db.hits - hits0
            rep["run_db_lookups"] = kernel.db.lookups - lookups0
            return rep
        return kernel, report


# ---------------------------------------------------------------------- #
# hybrid backend (adaptive per-partition packet/flow granularity)
# ---------------------------------------------------------------------- #
@register_engine("hybrid")
class HybridEngine(Engine):
    """HyGra-style adaptive granularity on the sharded packet loop: rate-
    stable partitions demote to a max-min-solver-driven flow-level lane and
    promote back on contention change (``repro.net.hybrid_sim``).  The
    third engine family — it trades *bounded* error for speed on unsteady
    traffic the pure-packet backends must simulate in full.

    opts:
      fidelity       "packet" (bit-identical to the sharded serial loop) |
                     "auto" (adaptive demote/promote, the default) |
                     "flow" (everything flow-level from t=0, coarsest)
      demote_after   stable samples before a partition demotes (auto mode)
      config         HybridConfig or dict merged over scenario.kernel
                     (foreign keys are ignored — scenarios share one
                     kernel-knob dict across backends)
      intra_workers  worker processes for heavy packet-lane fan-out, as in
                     the packet/wormhole backends

    ``RunResult.extras["granularity"]`` reports per-granularity event
    counts (packet_lane_events / flow_lane_events) and transition stats.
    """
    option_names = ("config", "demote_after", "fidelity", "intra_workers",
                    "record_rtt", "until", "validate")

    def run(self, scenario: Scenario, fidelity: str | None = None,
            demote_after: int | None = None, config=None,
            record_rtt=(), until: float = float("inf"),
            intra_workers: int = 1, validate: bool = False,
            **opts) -> RunResult:
        with span("repro.api.build"):
            if isinstance(config, HybridConfig):
                cfg = dataclasses.replace(config)    # never mutate the caller's
            else:
                cfg = HybridConfig.from_knobs({**scenario.kernel, **(config or {})})
            # explicit engine opts override the config; an unset opt must not
            # clobber a fidelity carried by config=/scenario.kernel
            if fidelity is not None:
                cfg.fidelity = fidelity
            if demote_after is not None:
                cfg.demote_after = demote_after
            if cfg.fidelity not in FIDELITIES:
                raise ValueError(f"unknown fidelity {cfg.fidelity!r}; "
                                 f"have {FIDELITIES}")
            plan = chaos_mod.plan_for(scenario)
            chaos_mod.check_backend(plan, self.name, intra_workers=intra_workers)
            topo = scenario.build_topology()
            kernel, report_fn = None, None
            if cfg.fidelity != "packet":
                kernel = HybridKernel(cfg)
                report_fn = kernel.report
            sim = HybridSim(topo, kernel=kernel, intra_workers=intra_workers,
                            validate=validate, **scenario.sim)
            sim.record_rtt_fids = set(record_rtt)
            driver = _drive(scenario, sim)
            if plan is not None and plan.has_link_events:
                plan.install(sim)
        with span("repro.engine.loop"):
            t0 = time.perf_counter()
            try:
                sim.run(until=until)
            finally:
                sim.close()
            wall = time.perf_counter() - t0
        result = _collect(self.name, scenario, sim, driver, wall,
                          kernel_report=report_fn() if report_fn else None,
                          record_rtt=record_rtt)
        result.extras["granularity"] = sim.granularity_report()
        result.extras["shard"] = sim.shard_report()
        return result


# ---------------------------------------------------------------------- #
# fluid backend (JAX rate dynamics; vmapped over batches)
# ---------------------------------------------------------------------- #
@register_engine("fluid")
class FluidEngine(Engine):
    """DCTCP-form fluid dynamics: per-phase converged rates turn into FCT
    estimates; the phase DAG is scheduled analytically on top.  Coarse: on
    the 128-GPU GPT row (flow scale 1/256) its mean FCT error against the
    packet oracle is 54.8%.  Cheap: there an iteration takes about 0.13 s
    on a TPU v5e host, the packet oracle 5.8 s; ``run_batch`` evaluates a
    whole padded sweep in one vmapped compilation (§6.1 multi-experiment
    parallelism).

    Each phase's ``FluidScenario.from_flows`` is the ``repro.fluid.build``
    span; ``fluid_converged_rates`` records the rest of the host path."""
    option_names = ("dt", "steps")
    on_device = True

    def run(self, scenario: Scenario, steps: int = 200, dt: float | None = None,
            **opts) -> RunResult:
        from repro.net.fluid_jax import FluidScenario, fluid_converged_rates
        with span("repro.api.build"):
            chaos_mod.check_backend(chaos_mod.plan_for(scenario), self.name)
            topo = scenario.build_topology()
            phases = scenario.build_phases()
        with span("repro.engine.loop"):
            t0 = time.perf_counter()
            fcts: dict[int, float] = {}
            flow_bytes: dict[int, float] = {}
            tags: dict[int, str] = {}
            done_t: list[float] = [0.0] * len(phases)
            total_steps = 0
            for i, ph in enumerate(phases):
                start = max((done_t[d] for d in set(ph.deps)), default=0.0) + ph.compute
                if scenario.kind == "flows":
                    start += ph.flows[0].start if ph.flows else 0.0
                end = start
                if ph.flows:
                    with span("repro.fluid.build"):
                        fs = FluidScenario.from_flows(
                            topo, [(f.fid, f.src, f.dst, f.size) for f in ph.flows])
                    rates = fluid_converged_rates(fs, steps=steps, dt=dt)["rates"]
                    total_steps += steps
                    for f, rate in zip(ph.flows, rates):
                        fct = f.size / max(float(rate), 1e3)
                        fcts[f.fid] = fct
                        flow_bytes[f.fid] = f.size
                        tags[f.fid] = f.tag
                        end = max(end, start + fct)
                done_t[i] = end
            wall = time.perf_counter() - t0
        with span("repro.api.collect"):
            iteration = max(done_t) if done_t else None
            return RunResult(backend=self.name, scenario=scenario.name,
                             fcts=fcts, flow_bytes=flow_bytes, tags=tags,
                             iteration_time=iteration, events_processed=total_steps,
                             wall_time=wall)

    def run_batch(self, scenarios: list[Scenario], steps: int = 200,
                  dt: float | None = None, **opts) -> list[RunResult]:
        """Pad + vmap: one compiled program evaluates every flow scenario's
        converged rates at once (workload scenarios fall back to a loop)."""
        from repro.net.fluid_jax import FluidScenario, sweep_converged_rates
        for s in scenarios:
            chaos_mod.check_backend(chaos_mod.plan_for(s), self.name)
        if any(s.kind != "flows" for s in scenarios):
            return [self.run(s, steps=steps, dt=dt, **opts) for s in scenarios]
        dt = dt if dt is not None else 1e-5    # vmapped path needs one shared dt
        t0 = time.perf_counter()
        fls = [FluidScenario.from_flows(
            s.build_topology(), [(f.fid, f.src, f.dst, f.size) for f in s.flows])
            for s in scenarios]
        per_scn_rates = sweep_converged_rates(fls, dt=dt, steps=steps)
        wall = time.perf_counter() - t0
        out = []
        for s, rates in zip(scenarios, per_scn_rates):
            fcts, rate_map = {}, {}
            for f, rate in zip(s.flows, rates):
                fcts[f.fid] = f.size / max(float(rate), 1e3)
                rate_map[f.fid] = float(rate)
            finishes = [f.start + fcts[f.fid] for f in s.flows]
            out.append(RunResult(
                backend=self.name, scenario=s.name, fcts=fcts,
                flow_bytes={f.fid: f.size for f in s.flows},
                tags={f.fid: f.tag for f in s.flows},
                iteration_time=(max(finishes) - min(f.start for f in s.flows))
                if finishes else None,
                events_processed=steps, wall_time=wall / len(scenarios),
                extras={"rates": rate_map, "batch_wall": wall}))
        return out


# ---------------------------------------------------------------------- #
# analytic backend (flow-level max-min fair sharing)
# ---------------------------------------------------------------------- #
@register_engine("analytic")
class AnalyticEngine(Engine):
    """Progressive max-min fair-share model — the flow-level abstraction the
    paper positions against (§2.2).  Shares the WorkloadDriver, so it runs
    the same phase DAGs the packet backends do."""
    option_names = ("until",)

    def run(self, scenario: Scenario, until: float = float("inf"),
            **opts) -> RunResult:
        with span("repro.api.build"):
            chaos_mod.check_backend(chaos_mod.plan_for(scenario), self.name)
            sim = AnalyticSim(scenario.build_topology())
            driver = _drive(scenario, sim)
        with span("repro.engine.loop"):
            t0 = time.perf_counter()
            sim.run(until=until)
            wall = time.perf_counter() - t0
        return _collect(self.name, scenario, sim, driver, wall)


# the learned engine lives in its own package (it has a training half the
# registry does not need); a plain import is safe in either import order —
# repro.learned.engine only pulls names already defined above
import repro.learned.engine  # noqa: F401
