"""Operations and bytes of the Mamba-2 language model, from its shapes.

Model FLOPs per token: the input projections (z, x, B, C, dt), the output
projection, the depthwise convolution, the chunked state-space scan at the
configured chunk length (intra-chunk C.B products and their weighted sum
over x, each chunk's state, the state's read-out) and the tied unembedding
over the live vocabulary. Norms, gates and the softmax are left out.
Training is three times the forward.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_flops_per_token(cfg: Dict[str, Any]) -> int:
    d, n, hp, cl = cfg["d_model"], cfg["d_state"], cfg["headdim"], cfg["chunk_size"]
    di = cfg["expand"] * d
    nh = di // hp
    proj = 2 * d * (2 * di + 2 * n + nh) + 2 * di * d
    conv = 2 * cfg["d_conv"] * (di + 2 * n)
    ssd = 2 * cl * n + 2 * cl * di + 2 * di * n + 2 * di * n
    return proj + conv + ssd


def forward_flops_per_token(cfg: Dict[str, Any]) -> int:
    return cfg["n_layer"] * layer_flops_per_token(cfg) + 2 * cfg["d_model"] * cfg["vocab_size"]


def tokens_per_round(cell: Dict[str, Any]) -> int:
    fed = cell["fed"]
    return fed["num_selected"] * fed["local_steps"] * fed["local_batch"] * cell["traffic"]["seq_len"]


def eval_tokens(cell: Dict[str, Any]) -> int:
    t = cell["traffic"]
    per = max(t["eval_sequences"] // t["num_clients"], 1)
    return per * t["num_clients"] * t["seq_len"]


def train_flops(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    return 3 * forward_flops_per_token(cfg) * tokens_per_round(cell)


def eval_flops(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    return forward_flops_per_token(cfg) * eval_tokens(cell)


def train_bytes(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    """A floor on the cohort program's traffic: each client step reads and
    writes the bfloat16 weights once and reads its int32 tokens."""
    fed = cell["fed"]
    steps = fed["num_selected"] * fed["local_steps"]
    return steps * (2 * 2 * cfg["params"] + fed["local_batch"] * cell["traffic"]["seq_len"] * 4)
