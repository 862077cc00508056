"""Operations and bytes of the Mamba-2 SSD scan (``models/mamba2.py``
``_ssd_chunked``) in one round of local training.

FLOPs per token and layer of the forward, the scan's terms of
``flops/mamba2.layer_flops_per_token`` at the configured chunk length cl:

  2·cl·n    the intra-chunk C·Bᵀ scores (the whole cl × cl square);
  2·cl·di   their decay-weighted sum over x;
  2·di·n    each chunk's terminal state, Σ_j dt_j·x_j ⊗ B_j;
  2·di·n    the read-out of the state entering the chunk, C_i · h.

Bytes: a floor on the scan's memory traffic. Per token and layer the forward
reads its float32 inputs once and writes its float32 output once:

  x        di floats (heads × headdim, after the convolution);
  dt       nh floats (the step of each head, after the softplus);
  B and C  n floats each (one group);
  y        di floats.

The backward reads the same inputs and y's cotangent and writes the inputs'
cotangents: about twice the forward's traffic, so training is three times
the forward, in FLOPs as in bytes. The remat recompute is not counted, nor
anything the scan materialises (decay and weight tensors): those lie above
the floor, and are what a fused kernel saves.
"""

from __future__ import annotations

from typing import Any, Dict

from flops.mamba2 import tokens_per_round

F32 = 4


def _dims(cfg: Dict[str, Any]):
    di = cfg["expand"] * cfg["d_model"]
    return di, cfg["d_state"], di // cfg["headdim"], cfg["chunk_size"]


def layer_flops_per_token(cfg: Dict[str, Any]) -> int:
    di, n, _, cl = _dims(cfg)
    return 2 * cl * n + 2 * cl * di + 2 * di * n + 2 * di * n


def layer_bytes_per_token(cfg: Dict[str, Any]) -> int:
    di, n, nh, _ = _dims(cfg)
    return F32 * (di + nh + 2 * n + di)


def train_flops(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    return 3 * layer_flops_per_token(cfg) * cfg["n_layer"] * tokens_per_round(cell)


def train_bytes(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    return 3 * layer_bytes_per_token(cfg) * cfg["n_layer"] * tokens_per_round(cell)
