"""StruM Pallas TPU kernels (Mosaic on TPU; interpret mode in CPU tests).

strum_matmul — tiled matmul streaming compressed StruM weights, in-VMEM
decode (the paper's accelerated PE, §IV-D.2, mapped to the TPU memory
hierarchy).  ``ops`` holds the jit'd wrappers (with ``variant=`` selecting
the general / maskfree / dense lowering), ``ref`` the pure-jnp oracles.
Variant *selection* lives in :mod:`repro.engine.registry` — model/serving
code should dispatch through :mod:`repro.engine` rather than importing
kernels directly.
"""
from repro.kernels.ops import (PALLAS_VARIANTS, default_interpret,
                               strum_gemv, strum_grouped_matmul, strum_matmul)
from repro.kernels.ref import strum_dequant_ref, strum_matmul_ref

__all__ = [
    "strum_matmul", "strum_gemv", "strum_grouped_matmul", "default_interpret",
    "PALLAS_VARIANTS", "strum_matmul_ref", "strum_dequant_ref",
]
