"""Smoke run of the simulator's main path on one TPU chip.

    python3 chip_smoke.py

One process, no children.  Phases, in order:

1. device   — JAX must come up on a TPU; on any other platform the script
              exits non-zero before any work and prints no result line.
2. main     — the paper's headline deployment, the Table-1 GPT row on 128
              GPUs with HPCC (``training_scenario(n_gpus=128)``, flow sizes
              cut to 1/256), through ``repro.api.run`` on packet, wormhole,
              hybrid, analytic and fluid.  Wormhole and hybrid must stay
              under 1% mean FCT error against packet.
3. fluid    — ``fluid_run`` outputs live on the TPU; ``FluidEngine.run_batch``
              evaluates 8 padded flow scenarios in one vmapped program and
              agrees with each scenario evaluated alone.
4. kernels  — maxmin, cca_step and steady_scan run compiled (their compiled
              text holds ``tpu_custom_call``) and agree with their host
              oracles within the stated tolerances.
5. learned  — fit on a seeded in-memory wormhole campaign on the device,
              refit for determinism, serve 256 queries through ``run_batch``.

Every check is printed with its value and bound; the script exits non-zero
after the last phase if any check failed, and otherwise prints one JSON line
``{"ok": true, "device": {...}}`` last.  The compile cache follows
``repro.compile_cache``; the compile seconds and cache hits are printed.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

FAILED: list[str] = []


def check(name: str, ok: bool, detail: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}", flush=True)
    if not ok:
        FAILED.append(name)


def rel_diff(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def compiled_text(fn, *args) -> str:
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


class CompileStats:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit records its retrieval as the compile)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------- #
def phase_device():
    import jax
    from repro import compile_cache
    compile_cache.enable()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX came up on {dev.platform!r}; nothing runs",
              file=sys.stderr)
        sys.exit(1)
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    return dev


def phase_main(scn):
    from repro.api import run
    print(f"\n[main] {scn.name}: flow sizes cut to 1/256 (the repo's default "
          f"flow scale); packet, wormhole, hybrid and analytic run on the "
          f"host and put no work on the device", flush=True)
    results = {}
    for backend in ("packet", "wormhole", "hybrid", "analytic", "fluid"):
        t0 = time.perf_counter()
        results[backend] = run(scn, backend=backend)
        r = results[backend]
        errs = r.fct_errors_vs(results["packet"])
        print(f"  {backend:9s} wall {time.perf_counter() - t0:8.3f} s  "
              f"events {r.events_processed:>9d}  iteration "
              f"{r.iteration_time * 1e3:.6f} ms  mean FCT err "
              f"{errs.mean() * 100:.4f}%  flows {len(r.fcts)}", flush=True)
    import numpy as np
    n = len(results["packet"].fcts)
    for backend in ("wormhole", "hybrid"):
        err = float(results[backend].fct_errors_vs(results["packet"]).mean())
        check(f"{backend} mean FCT err vs packet", err < 0.01,
              f"{err * 100:.4f}% (bound 1%)")
    for backend in ("fluid", "analytic"):
        f = np.array(list(results[backend].fcts.values()))
        err = float(results[backend].fct_errors_vs(results["packet"]).mean())
        check(f"{backend} FCTs finite", len(f) == n and bool(
            np.isfinite(f).all() and (f > 0).all()),
            f"{len(f)}/{n} flows, err vs packet {err * 100:.4f}% (no bound)")


def flow_scenarios(scn, topo, phases, k: int):
    """``k`` phases with traffic, each as a flow scenario: half from the
    start of the iteration and half from its end, whose phases are wider,
    so the batch pads the narrower ones."""
    from repro.api import Scenario
    from repro.net.flows import FlowSpec
    busy = [p for p in phases if p.flows]
    out = []
    for ph in busy[:k // 2] + busy[len(busy) - (k - k // 2):]:
        flows = [FlowSpec(f.fid, f.src, f.dst, f.size, 0.0, "dctcp")
                 for f in ph.flows]
        out.append(Scenario(f"{scn.name}-phase{len(out)}", scn.topology,
                            flows=flows))
    return out


def phase_fluid(scn, topo, phases):
    import jax.numpy as jnp
    from repro.api import get_engine
    from repro.net.fluid_jax import FluidScenario, fluid_run
    print("\n[fluid] on the device", flush=True)
    ph = next(p for p in phases if p.flows)
    fs = FluidScenario.from_flows(
        topo, [(f.fid, f.src, f.dst, f.size) for f in ph.flows])
    out = fluid_run(jnp.asarray(fs.incidence), jnp.asarray(fs.line_rate),
                    jnp.asarray(fs.base_rtt), jnp.asarray(fs.size),
                    jnp.asarray(fs.link_bw), 1e-5, 200)
    platforms = {d.platform for d in out["rates"].devices()}
    check("fluid_run outputs on the TPU", platforms == {"tpu"},
          f"platforms {sorted(platforms)}")

    scns = flow_scenarios(scn, topo, phases, 8)
    eng = get_engine("fluid")
    t0 = time.perf_counter()
    batch = eng.run_batch(scns)
    wall = time.perf_counter() - t0
    worst = 0.0
    for s, r in zip(scns, batch):
        alone = eng.run_batch([s])[0]
        worst = max(worst, rel_diff(list(r.extras["rates"].values()),
                                    list(alone.extras["rates"].values())))
    sizes = sorted({len(s.flows) for s in scns})
    print(f"  run_batch: {len(scns)} scenarios of {sizes} flows padded "
          f"into one vmapped program, {wall:.3f} s", flush=True)
    check("run_batch vs each scenario alone", worst < 1e-4,
          f"max rel rate diff {worst:.3e} (bound 1e-4)")


def phase_kernels(scn, topo, phases):
    import jax.numpy as jnp
    import numpy as np
    from repro.api import training_scenario
    from repro.core.steady import fluctuation_batch
    from repro.kernels.maxmin.kernel import maxmin_kernel
    from repro.kernels.maxmin.ops import (incidence_from_csr,
                                          maxmin_rates_arrays,
                                          maxmin_rates_jax, paths_to_arrays)
    from repro.kernels.steady_scan.ops import steady_scan
    from repro.net.fluid_jax import (FluidScenario, fluid_converged_rates,
                                     fluid_run)
    print("\n[kernels] compiled on the TPU vs host oracles", flush=True)

    # maxmin: every flow path of gpt@128 (1024) and moe@128 (4096)
    moe = training_scenario(n_gpus=128, moe=True)
    for s, t, phs in ((scn, topo, phases),
                      (moe, moe.build_topology(), moe.build_phases())):
        paths = {f.fid: t.route(f.src, f.dst, f.fid)
                 for ph in phs for f in ph.flows}
        _, links, off = paths_to_arrays(paths)
        inc, cap = incidence_from_csr(links, off, t.link_bw)
        text = compiled_text(lambda i, c: maxmin_kernel(i, c,
                                                        interpret=False),
                             inc, cap)
        check(f"maxmin compiled ({s.name}, {inc.shape[0]}x{inc.shape[1]})",
              "tpu_custom_call" in text, "tpu_custom_call in compiled text")
        t0 = time.perf_counter()
        ker = maxmin_rates_jax(links, off, t.link_bw, impl="kernel")
        t_ker = time.perf_counter() - t0
        exact = maxmin_rates_arrays(links, off, t.link_bw)
        ref = maxmin_rates_jax(links, off, t.link_bw, impl="ref")
        d = rel_diff(ker, exact)
        check(f"maxmin kernel vs exact solver ({inc.shape[0]} flows)",
              d <= 1e-4, f"max rel diff {d:.3e} (bound 1e-4); kernel vs "
              f"jax ref {rel_diff(ker, ref):.3e}; first call {t_ker:.3f} s")

    # cca_step: fluid dynamics with the kernel vs the inline jnp step
    all_flows = [(f.fid, f.src, f.dst, f.size) for ph in phases
                 for f in ph.flows]
    ph = next(p for p in phases if p.flows)
    for label, flows in (("one phase", [(f.fid, f.src, f.dst, f.size)
                                        for f in ph.flows]),
                         ("all phases", all_flows)):
        fs = FluidScenario.from_flows(topo, flows)
        args = [jnp.asarray(x) for x in (fs.incidence, fs.line_rate,
                                         fs.base_rtt, fs.size, fs.link_bw)]
        text = compiled_text(lambda *a: fluid_run(*a, 1e-5, 8,
                                                  use_kernel=True), *args)
        check(f"cca_step compiled ({label}, {fs.incidence.shape})",
              "tpu_custom_call" in text, "tpu_custom_call in compiled text")
        rk = fluid_converged_rates(fs, use_kernel=True)["rates"]
        inline = fluid_converged_rates(fs, use_kernel=False)
        d = rel_diff(rk, inline["rates"])
        check(f"cca_step kernel vs inline step ({label}, "
              f"{len(flows)} flows)", d <= 1e-4,
              f"max rel converged-rate diff {d:.3e} (bound 1e-4)")
    hist = inline["hist"]                           # [400 steps, 1024 flows]

    # steady_scan: the fluid rate history, and a seeded monitor at 100352
    rng = np.random.default_rng(0)
    for label, h in (("fluid history", np.ascontiguousarray(
                         hist[-128:].T, np.float32)),
                     ("seeded monitor", rng.uniform(
                         1e8, 1e10, (100352, 128)).astype(np.float32))):
        text = compiled_text(lambda x: steady_scan(x, 128, interpret=False),
                             jnp.asarray(h))
        check(f"steady_scan compiled ({label}, {h.shape})",
              "tpu_custom_call" in text, "tpu_custom_call in compiled text")
        fl, mean = steady_scan(h, 128)
        fl_o = fluctuation_batch(h)
        finite = np.isfinite(fl_o)
        same_inf = bool((np.isfinite(np.asarray(fl)) == finite).all())
        d_fl = rel_diff(np.asarray(fl)[finite], fl_o[finite])
        d_mean = rel_diff(mean, h.astype(np.float64).mean(axis=1))
        check(f"steady_scan vs core.steady ({label}, F={h.shape[0]})",
              same_inf and d_fl <= 1e-4 and d_mean <= 1e-5,
              f"fluct max rel diff {d_fl:.3e} (bound 1e-4), mean "
              f"{d_mean:.3e} (bound 1e-5), inf rows agree {same_inf}")


def phase_learned():
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.learned_bench import wave_scenario
    from repro.api import Campaign, get_engine, run
    from repro.learned import fit, heldout_fct_error
    from repro.learned import model as M
    print("\n[learned] fit and serve on the device", flush=True)
    family = [wave_scenario(0.5 + 0.075 * i, base_size=4e5, name=f"fam{i}")
              for i in range(20)]
    with Campaign.in_memory(name="chip-smoke-learned") as camp:
        camp.sweep(family, backend="wormhole")
        ds = camp.export_dataset()
    t0 = time.perf_counter()
    params = fit(ds, seed=0, steps=500)
    t_fit = time.perf_counter() - t0
    err = heldout_fct_error(params, ds)
    check("learned held-out FCT err", err < 0.10,
          f"{err * 100:.4f}% over {ds.n_heldout_records} held-out records "
          f"(bound 10%); fit {t_fit:.3f} s")
    again = fit(ds, seed=0, steps=500)
    check("learned fit deterministic", again.fingerprint == params.fingerprint,
          f"{params.fingerprint} vs {again.fingerprint}")
    x = jnp.zeros((1, params.d_in), jnp.float32)
    y = M.apply([(jnp.asarray(w), jnp.asarray(b)) for w, b in params.weights],
                x)
    check("learned model on the TPU",
          {d.platform for d in y.devices()} == {"tpu"},
          f"platforms {sorted(d.platform for d in y.devices())}")

    queries = [wave_scenario(0.55 + 1.3 * i / 255, base_size=4e5,
                             name=f"q{i}") for i in range(256)]
    eng = get_engine("learned")
    eng.run_batch(queries[:1], params=params)          # compile for serving
    t0 = time.perf_counter()
    served = eng.run_batch(queries, params=params)
    t_serve = time.perf_counter() - t0
    fcts = np.array([v for r in served for v in r.fcts.values()])
    check("learned served 256 queries", len(served) == 256 and bool(
        np.isfinite(fcts).all() and (fcts > 0).all()),
        f"{len(fcts)} flow FCTs in {t_serve:.3f} s "
        f"({len(served) / t_serve:.1f} queries/s)")
    qerr = []
    for q, r in list(zip(queries, served))[::64]:
        truth = run(q, backend="wormhole")
        qerr.append(float(r.fct_errors_vs(truth).mean()))
    check("learned query err vs wormhole", max(qerr) < 0.10,
          f"max {max(qerr) * 100:.4f}% over {len(qerr)} queries (bound 10%)")


def main() -> int:
    t_start = time.perf_counter()
    dev = phase_device()
    stats = CompileStats()
    from repro.api import training_scenario
    scn = training_scenario(n_gpus=128)
    topo = scn.build_topology()
    phases = scn.build_phases()
    phase_main(scn)
    phase_fluid(scn, topo, phases)
    phase_kernels(scn, topo, phases)
    phase_learned()
    print(f"\ncompile: {stats.seconds:.3f} s in backend compiles, "
          f"{stats.hits}/{stats.requests} persistent-cache hits; total "
          f"{time.perf_counter() - t_start:.3f} s", flush=True)
    if FAILED:
        print(f"FAILED: {', '.join(FAILED)}", file=sys.stderr)
        return 1
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
