"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk computation.

TARGET: TPU v5e. One program per (batch, chunk, head): the chunk-local
quadratic term and the chunk terminal state are computed in VMEM with fp32
accumulation; block shapes are (chunk, headdim) / (chunk, n_state), chunk a
multiple of 128 in production (tests sweep smaller shapes in interpret mode).

The cross-chunk recurrence (a (B, nh, hp, n)-sized lax.scan over chunks) and
the inter-chunk correction stay in jnp — they are O(S/chunk) small and
bandwidth-trivial next to the intra-chunk matmuls. ``ops.ssd_forward`` does
the composition; ``ref.ssd_reference`` is the exact sequential recurrence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, state_ref, cumlast_ref, *, chunk: int):
    """One (batch, chunk, head) program.

    x: (cl, hp); dt: (cl, 1); a: (1, 1); b/c: (cl, n).
    Outputs: y_intra (cl, hp); state (hp, n); cum_last (1, 1).
    """
    x = x_ref[0, 0].astype(jnp.float32)          # (cl, hp)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)  # (cl,)
    a = a_ref[0, 0, 0]                           # scalar A (negative)
    b = b_ref[0, 0].astype(jnp.float32)          # (cl, n)
    c = c_ref[0, 0].astype(jnp.float32)          # (cl, n)

    da = dt * a
    cum = jnp.cumsum(da)                          # (cl,)
    # decay[i, j] = exp(cum_i − cum_j) for j ≤ i else 0, masked in log space
    # before the exp: above the diagonal the exponent is ≥ 0 and overflows
    # to inf once the chunk's Σ dt·|A| passes ≈ 88 (inf · 0 would be NaN).
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(ii >= jj, cum[:, None] - cum[None, :], -jnp.inf))
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * decay * dt[None, :]              # (cl, cl)
    y_ref[0, 0] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(y_ref.dtype)

    # terminal state: Σ_j exp(cum_last − cum_j) · dt_j · x_j ⊗ b_j  → (hp, n)
    wj = jnp.exp(cum[-1] - cum) * dt              # (cl,)
    state_ref[0, 0] = jax.lax.dot_general(
        x * wj[:, None], b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(state_ref.dtype)
    cumlast_ref[0, 0, 0] = cum[-1]


def ssd_chunk(
    x: jax.Array,    # (B, NC, CL, NH, HP)
    dt: jax.Array,   # (B, NC, CL, NH)
    a_neg: jax.Array,  # (NH,)
    b_in: jax.Array,   # (B, NC, CL, N)
    c_in: jax.Array,   # (B, NC, CL, N)
    *,
    interpret: bool = False,
):
    """Intra-chunk SSD via Pallas. Returns (y_intra, states, cum_last).

    y_intra: (B, NC, CL, NH, HP); states: (B, NC, NH, HP, N);
    cum_last: (B, NC, NH) — per-chunk total log decay.
    """
    bsz, nc, cl, nh, hp = x.shape
    n = b_in.shape[-1]
    # head-major layouts for per-(b,c,h) programs
    xh = x.transpose(0, 1, 3, 2, 4).reshape(bsz * nc, nh, cl, hp)
    dth = dt.transpose(0, 1, 3, 2).reshape(bsz * nc, nh, cl, 1)
    ah = jnp.broadcast_to(a_neg[None], (bsz * nc, nh)).reshape(bsz * nc, nh, 1)
    bh = jnp.broadcast_to(b_in[:, :, None], (bsz, nc, nh, cl, n)).reshape(bsz * nc, nh, cl, n)
    ch = jnp.broadcast_to(c_in[:, :, None], (bsz, nc, nh, cl, n)).reshape(bsz * nc, nh, cl, n)

    kernel = functools.partial(_ssd_chunk_kernel, chunk=cl)
    y, states, cumlast = pl.pallas_call(
        kernel,
        grid=(bsz * nc, nh),
        in_specs=[
            pl.BlockSpec((1, 1, cl, hp), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, cl, 1), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, h: (g, h, 0)),
            pl.BlockSpec((1, 1, cl, n), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, cl, n), lambda g, h: (g, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cl, hp), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, hp, n), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, h: (g, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * nc, nh, cl, hp), jnp.float32),
            jax.ShapeDtypeStruct((bsz * nc, nh, hp, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz * nc, nh, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xh, dth, ah, bh, ch)

    y = y.reshape(bsz, nc, nh, cl, hp).transpose(0, 1, 3, 2, 4)
    states = states.reshape(bsz, nc, nh, hp, n)
    cumlast = cumlast.reshape(bsz, nc, nh)
    return y, states, cumlast
