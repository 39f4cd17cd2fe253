"""Pallas TPU kernels for the compute hot spots.

Each kernel package has three artifacts (see EXAMPLE.md):
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (compiled for the TPU; interpret=True
              runs the kernel body op-by-op, as the CPU tests do)
  ref.py    — pure-jnp oracle used by the allclose test sweeps
flash_attention has no kernel.py: its ops.py wraps JAX's splash attention
(forward, dq and dkv kernels), which the model's long causal attention runs
on one TPU (``repro.models.layers.attn_path``).

The simulator treats one pallas_call as one op (the paper's op-level
abstraction holds: kernels sit below the profiling granularity).
"""
from repro.kernels.flash_attention.ops import flash_attention  # noqa: F401
from repro.kernels.rmsnorm.ops import fused_rmsnorm  # noqa: F401
from repro.kernels.ssd_scan.ops import ssd_scan  # noqa: F401
