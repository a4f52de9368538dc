"""Microbenchmarks for the Pallas kernels (interpret mode on CPU — numbers
are correctness-path timings; TPU timings come from real hardware) and the
vectorized fluid engine vs the per-packet oracle."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def kernels():
    from repro.kernels.cca_step.ops import cca_step
    from repro.kernels.cca_step.ref import cca_step_ref
    from repro.kernels.steady_scan.ops import steady_scan
    from repro.kernels.steady_scan.ref import steady_scan_ref
    rows = []
    rng = np.random.default_rng(0)
    F, L = 1024, 256
    M = jnp.asarray((rng.random((F, L)) < 0.1).astype(np.float32))
    args = [jnp.asarray(rng.uniform(1e8, 1e10, F), jnp.float32) for _ in range(2)]
    args += [jnp.asarray(rng.uniform(0, 1, F), jnp.float32),
             jnp.zeros(F, jnp.float32),
             jnp.asarray(rng.uniform(1e6, 1e7, F), jnp.float32),
             jnp.full((F,), 12.5e9, jnp.float32),
             jnp.full((F,), 1e-5, jnp.float32), M,
             jnp.zeros(L, jnp.float32), jnp.full((L,), 12.5e9, jnp.float32)]
    t_k = _time(lambda *a: cca_step(*a, dt=1e-5), *args)
    t_r = _time(lambda *a: jax.jit(lambda *x: cca_step_ref(*x, dt=1e-5))(*a), *args)
    rows.append(("kernel/cca_step_interp", t_k * 1e6,
                 {"ref_us": round(t_r * 1e6, 1), "flows": F, "links": L}))

    hist = jnp.asarray(rng.uniform(1e8, 1e10, (4096, 64)), jnp.float32)
    t_k = _time(lambda h: steady_scan(h, 64), hist)
    t_r = _time(jax.jit(lambda h: steady_scan_ref(h, 64)), hist)
    rows.append(("kernel/steady_scan_interp", t_k * 1e6,
                 {"ref_us": round(t_r * 1e6, 1), "flows": 4096}))
    return rows


def fluid_vs_oracle():
    from repro.net.fluid_jax import FluidScenario, fluid_run
    from repro.net.packet_sim import PacketSim
    from repro.net.flows import FlowSpec
    from repro.net.topology import leaf_spine_clos
    topo = leaf_spine_clos(32, leaf_down=8, n_spines=4)
    flows = [(i, i, 24 + i % 4, 4e6) for i in range(16)]
    t0 = time.perf_counter()
    sim = PacketSim(topo)
    for fid, s, d, sz in flows:
        sim.add_flow(FlowSpec(fid, s, d, sz, 0.0, "dctcp"))
    sim.run()
    t_oracle = time.perf_counter() - t0
    scn = FluidScenario.from_flows(topo, flows)
    args = (jnp.asarray(scn.incidence), jnp.asarray(scn.line_rate),
            jnp.asarray(scn.base_rtt), jnp.asarray(scn.size),
            jnp.asarray(scn.link_bw))
    t_fluid = _time(lambda *a: fluid_run(*a, 1e-5, 200), *args)
    return [("fluid/engine_vs_oracle", t_fluid * 1e6,
             {"oracle_s": round(t_oracle, 2),
              "fluid_speedup": round(t_oracle / t_fluid, 1),
              "oracle_events": sim.events_processed})]


def vmapped_sweep():
    from repro.net.fluid_jax import FluidScenario, converged_phase_rates
    from repro.net.topology import leaf_spine_clos
    topo = leaf_spine_clos(32, leaf_down=8, n_spines=4)
    scns = [FluidScenario.from_flows(
        topo, [(i, i, 24 + (i + j) % 4, 4e6) for i in range(8)])
        for j in range(16)]
    t = _time(lambda: converged_phase_rates(scns, steps=100, dt=1e-5))
    return [("fluid/vmap_16_experiments", t * 1e6,
             {"per_experiment_us": round(t * 1e6 / 16, 1)})]


ALL = [kernels, fluid_vs_oracle, vmapped_sweep]
