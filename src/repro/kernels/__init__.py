"""Pallas TPU kernels for the paper's compute hot spot (the revise contraction).

rtac_support   revise sweep + in-kernel fixpoint, dense int8 (MXU) or
               bitpacked int32 words (VPU) — one body, the encoding a parameter
ops            jit'd wrappers + padding/packing + network builders, and the
               one place the interpret/compile decision is made
autotune       per-bucket instance tiling (block_r) under a VMEM budget
ref            pure-jnp oracles the kernels are validated against
"""

from . import ops, ref, rtac_support

__all__ = ["ops", "ref", "rtac_support"]
