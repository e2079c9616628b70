"""Pallas TPU kernels for the serving hot spots.

Each kernel package has three modules:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (shape plumbing, interpret switch)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Tests run on the CPU with ``JAX_PLATFORMS=cpu`` and validate the kernels
with ``interpret=True``; ``tests/test_tpu_compile.py`` compiles them for a
described TPU v5e, and ``chip_smoke.py`` runs the scoring kernel compiled on
the chip. The model forward paths use the jnp reference implementations so
the dry-run HLO stays analyzable; ``use_flash_kernel`` swaps the attention
kernel in.
"""
