"""Bytes the fused HeteRo-Select kernels must move for K clients.

The selection runs two Pallas passes over one stacked (9, Kpad) operand of
client metadata (bfloat16 when the client state is compact, else float32),
Kpad being K rounded up to whole blocks of at most 32768 lanes:

  stats pass   reads the operand; writes one 128-lane partial per block;
  select pass  reads the operand, the (1, Kpad) float32 Gumbel row and the
               scalar row; writes scores, softmax numerators and perturbed
               logits as (1, Kpad) float32 rows and one partial per block.

Their few operations per byte make them bandwidth-bound, so the least time
is these bytes over the chip's memory bandwidth.
"""

from __future__ import annotations

LANE, MAX_BLOCK, NROWS = 128, 32768, 9


def layout(k: int):
    """(block, nblocks, kpad) as the kernels lay K out."""
    kpad_lane = -(-k // LANE) * LANE
    blk = min(MAX_BLOCK, kpad_lane)
    nblocks = -(-kpad_lane // blk)
    return blk, nblocks, nblocks * blk


def kernel_bytes(k: int, state_itemsize: int) -> int:
    """Bytes both passes read and write for one selection."""
    _, nblocks, kpad = layout(k)
    operand = NROWS * kpad * state_itemsize
    partials = nblocks * LANE * 4
    scalars = LANE * 4
    stats = operand + scalars + partials
    select = operand + scalars + kpad * 4 + 3 * kpad * 4 + partials
    return stats + select
