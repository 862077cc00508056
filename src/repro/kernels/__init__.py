"""Pallas TPU kernels, compiled by Mosaic on TPU (``score_select`` is
compile-checked for v5e in tests/test_tpu_compile.py); ``interpret=True``
runs them on CPU, for tests only (``ops.kernel_interpret`` decides).

flash_attention — blockwise online-softmax attention (prefill hot spot)
ssd_scan        — Mamba-2 SSD intra-chunk grouped matmuls
score_select    — fused HeteRo-Select scoring + softmax (the paper's Eqs 1–12)
moe_gmm         — MegaBlocks-style grouped matmul (scalar-prefetch expert tiles)
"""

from repro.kernels.moe_gmm import grouped_matmul
from repro.kernels.ops import flash_mha, ssd_forward, heterosel_probs

__all__ = ["flash_mha", "ssd_forward", "heterosel_probs", "grouped_matmul"]
