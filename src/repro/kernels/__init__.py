"""Pallas TPU kernels for the perf-critical compute layers:

  maxmin          — fixed-point max-min water-filling over a VMEM-resident
                    flow × link incidence
  cca_step        — fused congestion-control fluid step + incidence-matmul
                    queue aggregation (the packet loop's hot path, batched)
  steady_scan     — windowed rate-fluctuation detection (§5.1.2) over the
                    (flows × history) monitor buffer
  flash_attention — blockwise online-softmax attention (causal / sliding
                    window / GQA) for the architecture zoo

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with padding/dispatch) and ref.py (pure-jnp oracle used by tests).
Kernels are validated in interpret mode on CPU, compiled for a described
TPU v5e by tests/test_chip_compile.py, and run on the chip by
chip_smoke.py; BlockSpecs are sized for TPU VMEM (see per-kernel notes)."""
