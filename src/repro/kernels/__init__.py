"""Pallas TPU kernels for the compute hot spots.

Each kernel ships as <name>/kernel.py (pl.pallas_call + explicit BlockSpec
VMEM tiling), <name>/ops.py (jit'd wrapper with XLA fallback) and
<name>/ref.py (pure-jnp oracle).  Kernels target TPU (MXU-aligned tiles);
the tests validate them with interpret=True on the CPU platform.  The
fleet kernel (fleet_step.py) does not compile for TPU yet: Mosaic refuses
it, and it runs only in interpret mode.
"""
