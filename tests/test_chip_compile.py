"""The Pallas kernels compile for a TPU v5e chip at the main path's widths
and at the ceilings their docstrings name, and so does the fluid backend's
one program per iteration at the 128-GPU rows' padded shapes.

Nothing runs: each wrapper is lowered with ``interpret=False`` against a
described (not attached) ``v5e:2x2`` topology and compiled by the TPU
compiler that ships with jax, which refuses what the chip would refuse
(tile layouts, VMEM overflow).  The topology is described inside a fixture,
never at import: only one process at a time may load the TPU library, and
the workers that are not given this file must collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cca_step.ops import cca_step
from repro.kernels.maxmin.kernel import MAX_INCIDENCE_BYTES, maxmin_kernel
from repro.kernels.steady_scan.ops import steady_scan
from repro.net.fluid_jax import fluid_run_phases


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip compile written to the persistent cache cannot be read back
    # without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("F,L", [(1024, 128), (10240, 128)])
def test_maxmin_compiles(one_chip, F, L):
    text = _compiled_text(lambda inc, cap: maxmin_kernel(inc, cap,
                                                         interpret=False),
                          one_chip, (F, L), (L,))
    assert "tpu_custom_call" in text


def test_maxmin_refuses_over_vmem_budget(one_chip):
    F = MAX_INCIDENCE_BYTES // (4 * 1024) + 512     # just over, at L=1024
    with pytest.raises(ValueError, match="VMEM budget"):
        _compiled_text(lambda inc, cap: maxmin_kernel(inc, cap,
                                                      interpret=False),
                       one_chip, (F, 1024), (1024,))


@pytest.mark.parametrize("F,L", [(1024, 128), (4096, 1024)])
def test_cca_step_compiles(one_chip, F, L):
    def step(R, W, alpha, dlv, size, line, rtt0, M, q, bw):
        return cca_step(R, W, alpha, dlv, size, line, rtt0, M, q, bw,
                        dt=1e-5, interpret=False)
    text = _compiled_text(step, one_chip, *[(F,)] * 7, (F, L), (L,), (L,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("F,H", [(1024, 128), (100352, 128)])
def test_steady_scan_compiles(one_chip, F, H):
    text = _compiled_text(lambda h: steady_scan(h, 16, interpret=False),
                          one_chip, (F, H))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("P,F,L", [(28, 64, 64), (36, 128, 96)])
def test_fluid_run_phases_compiles(one_chip, P, F, L):
    """The fluid backend's whole iteration, padded as gpt@128 and moe@128
    pad it, is one program the chip's compiler accepts."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((P, F, L), (P, F), (P, F), (P, F), (P, L), (P,))]
    compiled = fluid_run_phases.lower(*args, steps=200, window=20).compile()
    assert compiled.out_info.shape == (P, 20, F)
