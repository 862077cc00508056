"""Pallas TPU kernels: fused HeteRo-Select scoring, softmax and top-m
selection (paper Eqs 1–12) at population scale.

The paper's federation has 12 clients; production cross-device federations
have 10⁴–10⁶. At that scale the six score components + softmax over K
clients become a fused two-pass kernel over a real multi-block grid:

  * pass 1 (``_stats_kernel``): each grid step reduces one VMEM block of the
    stacked client metadata to five lane-slotted partials (loss min/max,
    Σ‖Δw‖² and observation count for the norm penalty, participation max);
    block i writes lanes ``[i·LANE, (i+1)·LANE)`` of a ``(1, nblocks·LANE)``
    partial row (a (1, LANE) block is legal on the TPU at any block count),
    combined into global statistics with a handful of O(nblocks) jnp
    reductions.
  * pass 2 (``_select_kernel`` / ``_score_kernel``): blocks stream through
    VMEM again computing scores, block-local softmax exponentials with a
    flash-attention-style (m_b, l_b) normalizer merge, and — in the fused
    selection variant — the Gumbel-perturbed logits ``z + g``. Mosaic has no
    in-kernel ``top_k``, so the Gumbel-top-m cut itself runs in XLA over
    that (K,) perturbed vector: one ``lax.top_k``, the same ranking (and
    the same cohort) as ``core.selection.sample_clients``.

All (K,) operands travel as ONE stacked ``(NROWS, Kpad)`` array padded once
(bf16 when the ClientState is bf16 — see ``core.state.to_bf16`` — so a
K=10⁶ federation feeds the kernel ~18 MB, not 8 separate f32 pads). Row
``ROW_STALE`` carries the async engine's clock-measured staleness override
(Eq 7); a scalar lane toggles it so sync and async share one kernel.

``segmented_score_probs`` scores E block-aligned edge slices in a single
grid=(E,) launch for the hierarchical engine's inner selection, and
``sharded_score_select`` distributes state + scoring over a client device
axis via shard_map, with cross-shard collectives for the min/max/mean
statistics, the softmax normalizer, and the top-m candidate merge.

VALIDATED against ``repro.core.scoring`` + softmax (the paper-faithful jnp
implementation) in tests/test_kernels.py (interpret mode, CPU); every
kernel here compiles for a TPU v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.scoring import HeteRoScoreConfig

LANE = 128          # TPU lane width — every padded extent is a multiple
MAX_BLOCK = 32768   # widest client block streamed through VMEM per grid step
BIG = 1e30

# Row layout of the stacked (NROWS, Kpad) operand: the eight ClientState
# vectors in ``core.state.score_inputs`` order + the staleness-override row.
(ROW_LOSS, ROW_LOSS2, ROW_JS, ROW_CNT, ROW_LAST, ROW_SQ, ROW_HASL,
 ROW_HASM, ROW_STALE) = range(9)
NROWS = 9

# Scalar lanes of the (1, LANE) f32 scalar operand (pass-2 kernels).
(SC_T, SC_TAU, SC_USEOV, SC_LMIN, SC_LMAX, SC_AVGSQ, SC_HMAX, SC_OFF,
 SC_KLIM) = range(9)

# Lane slots of each block's LANE-wide slice of the pass-1 partial row.
(ST_LMIN, ST_LMAX, ST_SUMSQ, ST_NOBS, ST_HMAX) = range(5)


def _layout(k: int, block: Optional[int]) -> tuple[int, int, int]:
    """(block, nblocks, kpad) — block floored to a LANE multiple and clamped
    so a single-block launch is used whenever K fits one VMEM block."""
    kpad_lane = -(-k // LANE) * LANE
    blk = block or MAX_BLOCK
    blk = max(LANE, (blk // LANE) * LANE)
    blk = min(blk, kpad_lane)
    nblocks = -(-kpad_lane // blk)
    return blk, nblocks, nblocks * blk


def _pack(rows, staleness_override, k: int, kpad: int) -> jax.Array:
    """One stacked (NROWS, kpad) operand, padded once.

    Feed dtype follows the state: a bf16 ClientState streams as bf16 (the
    per-block f32 upcast happens in-register inside the kernel), so no
    per-client f32 duplicate is ever materialized at large K.
    """
    feed = jnp.bfloat16 if rows[0].dtype == jnp.bfloat16 else jnp.float32
    if staleness_override is None:
        stale = jnp.zeros((k,), feed)
    else:
        stale = jnp.asarray(staleness_override).astype(feed)
    stacked = jnp.stack([r.astype(feed) for r in rows] + [stale])
    return jnp.pad(stacked, ((0, 0), (0, kpad - k)))


def _scalar_row(t, tau, use_ov, lmin, lmax, avgsq, hmax, off, klim) -> jax.Array:
    vals = jnp.stack([jnp.asarray(v, jnp.float32) for v in
                      (t, tau, use_ov, lmin, lmax, avgsq, hmax, off, klim)])
    return jnp.zeros((LANE,), jnp.float32).at[:vals.shape[0]].set(vals).reshape(1, LANE)


def _lane_put(shape_lanes: int, j: int, v) -> jax.Array:
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, shape_lanes), 1)
    return jnp.where(lane == j, v, 0.0)


def _stats_kernel(state_ref, scal_ref, out_ref, *, block: int):
    """Pass 1: per-block partials for the cross-block scoring statistics."""
    i = pl.program_id(0)
    off = scal_ref[0, SC_OFF].astype(jnp.int32)
    klim = scal_ref[0, SC_KLIM].astype(jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) + i * block + off
    valid = col < klim

    loss = state_ref[ROW_LOSS:ROW_LOSS + 1, :].astype(jnp.float32)
    sq = state_ref[ROW_SQ:ROW_SQ + 1, :].astype(jnp.float32)
    cnt = state_ref[ROW_CNT:ROW_CNT + 1, :].astype(jnp.float32)
    obs = valid & (state_ref[ROW_HASL:ROW_HASL + 1, :].astype(jnp.float32) > 0)

    out_ref[...] = (
        _lane_put(LANE, ST_LMIN, jnp.min(jnp.where(obs, loss, BIG)))
        + _lane_put(LANE, ST_LMAX, jnp.max(jnp.where(obs, loss, -BIG)))
        + _lane_put(LANE, ST_SUMSQ, jnp.sum(jnp.where(obs, sq, 0.0)))
        + _lane_put(LANE, ST_NOBS, jnp.sum(jnp.where(obs, 1.0, 0.0)))
        + _lane_put(LANE, ST_HMAX, jnp.max(jnp.where(valid, cnt, 0.0)))
    )


def _combine_stats(stats: jax.Array):
    """Fold the (nblocks, LANE) partial table into the four global scalars.

    min-of-mins / max-of-maxes are exact; the Σ‖Δw‖² recombination differs
    from a monolithic jnp.sum only in f32 summation order.
    """
    lmin = jnp.min(stats[:, ST_LMIN])
    lmax = jnp.max(stats[:, ST_LMAX])
    avgsq = jnp.sum(stats[:, ST_SUMSQ]) / jnp.maximum(jnp.sum(stats[:, ST_NOBS]), 1.0)
    hmax = jnp.maximum(jnp.max(stats[:, ST_HMAX]), 1.0)
    return lmin, lmax, avgsq, hmax


def _block_scores(rows, sc, cfg: HeteRoScoreConfig) -> jax.Array:
    """Six score components + Eq (1) additive combination for one block.

    ``rows(j)`` yields the (1, block) f32 view of stacked row j and ``sc(j)``
    scalar lane j; the global statistics arrive pre-reduced in those lanes.
    """
    t = sc(SC_T)
    loss = rows(ROW_LOSS)
    loss2 = rows(ROW_LOSS2)
    has_loss = rows(ROW_HASL) > 0
    has_mom = rows(ROW_HASM) > 0

    # Eq (3): min-max normalized information value (neutral 0.5 if unseen)
    lmin = sc(SC_LMIN)
    lmax = sc(SC_LMAX)
    v = jnp.clip((loss - lmin) / (lmax - lmin + 1e-8), 0.0, 1.0)
    v = jnp.where(has_loss, v, 0.5)

    # Eq (4): diversity with decaying weight
    decay = 2.0 * (1.0 - 0.5 * jnp.minimum(t / cfg.diversity_decay_rounds, 1.0))
    div = rows(ROW_JS) * decay

    # Eq (5): sigmoid momentum
    m = jnp.where(has_mom, (loss2 - loss) / (loss2 + 1e-8), 0.0)
    mom = 2.0 / (1.0 + jnp.exp(-5.0 * m)) - 0.5

    # Eq (6): fairness
    fair = (1.0 + cfg.eta * rows(ROW_CNT) / sc(SC_HMAX)) ** (-2)

    # Eq (7): staleness — round-counter Δ or the async clock override row
    use_ov = sc(SC_USEOV)
    delta = jnp.where(use_ov > 0,
                      jnp.maximum(rows(ROW_STALE), 0.0),
                      jnp.maximum(t - rows(ROW_LAST), 0.0))
    delta = jnp.minimum(delta, float(cfg.t_max))
    st = 1.0 + cfg.gamma * jnp.log1p(delta)

    # Eq (11): update-norm penalty
    r = jnp.where(has_loss, rows(ROW_SQ) / (sc(SC_AVGSQ) + 1e-8), 1.0)
    npen = 1.0 - cfg.alpha * (2.0 / (1.0 + jnp.exp(-3.0 * r)) - 1.0)

    # Eq (1) additive combination (Eqs 8–10 shift the modulating factors)
    return (cfg.w_value * v + cfg.w_diversity * div + cfg.w_momentum * mom
            + cfg.w_fairness * (fair - 1.0) + cfg.w_staleness * (st - 1.0)
            + cfg.w_norm * (npen - 1.0))


def _rows_fn(state_ref):
    return lambda j: state_ref[j:j + 1, :].astype(jnp.float32)


def _score_body(state_ref, scal_ref, *, cfg, block):
    """Shared pass-2 prologue: scores, block softmax exponentials, (m_b, l_b)."""
    i = pl.program_id(0)
    off = scal_ref[0, SC_OFF].astype(jnp.int32)
    klim = scal_ref[0, SC_KLIM].astype(jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) + i * block + off
    valid = col < klim
    s = _block_scores(_rows_fn(state_ref), lambda j: scal_ref[0, j], cfg)
    z = jnp.where(valid, s / scal_ref[0, SC_TAU], -BIG)
    m_b = jnp.max(z)
    e = jnp.where(valid, jnp.exp(z - m_b), 0.0)
    return s, z, e, m_b


def _score_kernel(state_ref, scal_ref, scores_ref, e_ref, part_ref, *,
                  cfg: HeteRoScoreConfig, block: int):
    s, _, e, m_b = _score_body(state_ref, scal_ref, cfg=cfg, block=block)
    scores_ref[...] = s
    e_ref[...] = e
    part_ref[...] = _lane_put(LANE, 0, m_b) + _lane_put(LANE, 1, jnp.sum(e))


def _select_kernel(state_ref, scal_ref, gumbel_ref, scores_ref, e_ref,
                   part_ref, pert_ref, *, cfg: HeteRoScoreConfig, block: int):
    """Pass 2 + the Gumbel-perturbed logits the top-m cut ranks (in XLA)."""
    s, z, e, m_b = _score_body(state_ref, scal_ref, cfg=cfg, block=block)
    scores_ref[...] = s
    e_ref[...] = e
    part_ref[...] = _lane_put(LANE, 0, m_b) + _lane_put(LANE, 1, jnp.sum(e))
    # Ranking z + g equals ranking log p + g (constant −logsumexp shift), so
    # no normalizer is needed; padding lanes keep z = −BIG and never rank.
    pert_ref[...] = z + gumbel_ref[...].astype(jnp.float32)


def _segment_kernel(state_ref, size_ref, scal_ref, probs_ref, scores_ref, *,
                    cfg: HeteRoScoreConfig, seg: int):
    """One edge slice per grid step: stats + scores + softmax fully in-block.

    Per-edge statistics (loss min/max, norm average, participation max) are
    reduced over that edge's ``size_e`` valid rows only — exactly what the
    per-edge jnp path computes on its gathered sub-state. ``size_ref`` is
    edge e's LANE-wide slice of the sizes row (the size sits in lane 0).
    """
    col = jax.lax.broadcasted_iota(jnp.int32, (1, seg), 1)
    valid = col < size_ref[0, 0].astype(jnp.int32)
    rows = _rows_fn(state_ref)
    loss = rows(ROW_LOSS)
    sq = rows(ROW_SQ)
    obs = valid & (rows(ROW_HASL) > 0)
    edge_stats = {
        SC_LMIN: jnp.min(jnp.where(obs, loss, BIG)),
        SC_LMAX: jnp.max(jnp.where(obs, loss, -BIG)),
        SC_AVGSQ: jnp.sum(jnp.where(obs, sq, 0.0)) / jnp.maximum(
            jnp.sum(jnp.where(obs, 1.0, 0.0)), 1.0),
        SC_HMAX: jnp.maximum(
            jnp.max(jnp.where(valid, rows(ROW_CNT), 0.0)), 1.0),
    }

    def sc(j):
        return edge_stats[j] if j in edge_stats else scal_ref[0, j]

    s = _block_scores(rows, sc, cfg)
    scores_ref[...] = s
    z = jnp.where(valid, s / sc(SC_TAU), -BIG)
    e = jnp.where(valid, jnp.exp(z - jnp.max(z)), 0.0)
    probs_ref[...] = e / jnp.maximum(jnp.sum(e), 1e-30)


def _normalize(e_flat: jax.Array, part: jax.Array, nblocks: int,
               block: int) -> jax.Array:
    """Merge per-block (m_b, l_b) into global probabilities.

    probs = e_block · exp(m_b − M) / L with M = max m_b and
    L = Σ l_b·exp(m_b − M) — the flash-attention normalizer merge. With a
    single block this reduces to e / Σe bitwise (scale = exp(0) = 1).
    """
    m_b = part[:, 0]
    l_b = part[:, 1]
    mglob = jnp.max(m_b)
    scale = jnp.exp(m_b - mglob)
    lglob = jnp.maximum(jnp.sum(l_b * scale), 1e-30)
    return (e_flat.reshape(nblocks, block) * scale[:, None] / lglob).reshape(-1)


def _run_stats(stacked, scal0, *, nblocks, block, interpret):
    kernel = functools.partial(_stats_kernel, block=block)
    return pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((NROWS, block), lambda i: (0, i)),
                  pl.BlockSpec((1, LANE), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, LANE), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nblocks * LANE), jnp.float32),
        interpret=interpret,
    )(stacked, scal0).reshape(nblocks, LANE)


def _run_select(stacked, scal, gpad, *, cfg, nblocks, block, interpret):
    """Pass 2 with the Gumbel row: ``(scores, e, part, pert)``, where
    ``part`` is the (nblocks, LANE) (m_b, l_b) table and the others are
    ``(1, nblocks·block)`` rows."""
    kpad = nblocks * block
    kernel = functools.partial(_select_kernel, cfg=cfg, block=block)
    row = pl.BlockSpec((1, block), lambda i: (0, i))
    scores, e, part, pert = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((NROWS, block), lambda i: (0, i)),
                  pl.BlockSpec((1, LANE), lambda i: (0, 0)),
                  row],
        out_specs=[row, row, pl.BlockSpec((1, LANE), lambda i: (0, i)), row],
        out_shape=[jax.ShapeDtypeStruct((1, kpad), jnp.float32),
                   jax.ShapeDtypeStruct((1, kpad), jnp.float32),
                   jax.ShapeDtypeStruct((1, nblocks * LANE), jnp.float32),
                   jax.ShapeDtypeStruct((1, kpad), jnp.float32)],
        interpret=interpret,
    )(stacked, scal, gpad)
    return scores, e, part.reshape(nblocks, LANE), pert


def fused_score_probs(
    loss_prev, loss_prev2, label_js, part_count, last_selected,
    update_sqnorm, has_loss, has_momentum,
    *, round_idx, tau, cfg: HeteRoScoreConfig,
    staleness_override=None, interpret: bool = False,
    block: Optional[int] = None,
):
    """Fused scores + selection probabilities for K clients (any K).

    Returns ``(probs, scores)``, both ``(K,)`` f32. ``staleness_override``
    substitutes a clock-measured (K,) Δ for the round-counter staleness in
    Eq (7) — the async engine's path. ``block`` overrides the VMEM block
    width (testing / tuning); default streams 32768-client blocks.
    """
    k = loss_prev.shape[0]
    blk, nblocks, kpad = _layout(k, block)
    rows = (loss_prev, loss_prev2, label_js, part_count, last_selected,
            update_sqnorm, has_loss, has_momentum)
    stacked = _pack(rows, staleness_override, k, kpad)
    t = jnp.asarray(round_idx, jnp.float32)
    use_ov = 0.0 if staleness_override is None else 1.0
    scal0 = _scalar_row(t, tau, use_ov, 0.0, 0.0, 0.0, 1.0, 0.0, k)
    stats = _run_stats(stacked, scal0, nblocks=nblocks, block=blk,
                       interpret=interpret)
    lmin, lmax, avgsq, hmax = _combine_stats(stats)
    scal = _scalar_row(t, tau, use_ov, lmin, lmax, avgsq, hmax, 0.0, k)

    kernel = functools.partial(_score_kernel, cfg=cfg, block=blk)
    scores, e, part = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((NROWS, blk), lambda i: (0, i)),
                  pl.BlockSpec((1, LANE), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, blk), lambda i: (0, i)),
                   pl.BlockSpec((1, blk), lambda i: (0, i)),
                   pl.BlockSpec((1, LANE), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, kpad), jnp.float32),
                   jax.ShapeDtypeStruct((1, kpad), jnp.float32),
                   jax.ShapeDtypeStruct((1, nblocks * LANE), jnp.float32)],
        interpret=interpret,
    )(stacked, scal)
    probs = _normalize(e.reshape(-1), part.reshape(nblocks, LANE), nblocks, blk)
    return probs[:k], scores.reshape(-1)[:k]


def fused_score_select(
    loss_prev, loss_prev2, label_js, part_count, last_selected,
    update_sqnorm, has_loss, has_momentum,
    *, round_idx, tau, m: int, key, cfg: HeteRoScoreConfig,
    staleness_override=None, interpret: bool = False,
    block: Optional[int] = None,
):
    """Fused scoring + softmax + Gumbel-top-m selection.

    Returns ``(selected_idx, probs, scores)`` — ``selected_idx`` is ``(m,)``
    int32. The Gumbel noise is drawn host-side with the exact shape/dtype
    ``core.selection.sample_clients`` uses, so for the same key the fused
    selection matches the jnp path (ranking z + g ≡ ranking log p + g).
    The kernel emits the perturbed logits; one XLA ``top_k`` cuts them.
    """
    k = loss_prev.shape[0]
    blk, nblocks, kpad = _layout(k, block)
    rows = (loss_prev, loss_prev2, label_js, part_count, last_selected,
            update_sqnorm, has_loss, has_momentum)
    stacked = _pack(rows, staleness_override, k, kpad)
    t = jnp.asarray(round_idx, jnp.float32)
    use_ov = 0.0 if staleness_override is None else 1.0
    scal0 = _scalar_row(t, tau, use_ov, 0.0, 0.0, 0.0, 1.0, 0.0, k)
    stats = _run_stats(stacked, scal0, nblocks=nblocks, block=blk,
                       interpret=interpret)
    lmin, lmax, avgsq, hmax = _combine_stats(stats)
    scal = _scalar_row(t, tau, use_ov, lmin, lmax, avgsq, hmax, 0.0, k)

    gumbel = jax.random.gumbel(key, (k,), jnp.float32)
    gpad = jnp.pad(gumbel, (0, kpad - k)).reshape(1, kpad)
    scores, e, part, pert = _run_select(stacked, scal, gpad, cfg=cfg,
                                        nblocks=nblocks, block=blk,
                                        interpret=interpret)
    probs = _normalize(e.reshape(-1), part, nblocks, blk)[:k]
    _, selected = jax.lax.top_k(pert.reshape(-1), m)
    return selected, probs, scores.reshape(-1)[:k]


def segmented_score_probs(
    loss_prev, loss_prev2, label_js, part_count, last_selected,
    update_sqnorm, has_loss, has_momentum,
    *, sizes, round_idx, tau, cfg: HeteRoScoreConfig, seg: int,
    staleness_override=None, interpret: bool = False,
):
    """Per-edge fused scoring for E block-aligned edge slices in ONE launch.

    Inputs are ``(E·seg,)`` arrays laid out edge-major — edge e's members
    occupy ``[e·seg, e·seg + sizes[e])``, the rest of each slice is padding
    (``seg`` must be a LANE multiple). Each grid step reduces and scores one
    edge independently, reproducing the per-edge jnp path's statistics and
    softmax. Returns ``(probs, scores)`` in the same ``(E·seg,)`` layout
    (padding slots hold probability 0).
    """
    if seg % LANE:
        raise ValueError(f"seg must be a multiple of {LANE}, got {seg}")
    num_edges = int(sizes.shape[0])
    k_total = num_edges * seg
    if loss_prev.shape[0] != k_total:
        raise ValueError(
            f"edge-major operands must be (E*seg,) = ({k_total},), "
            f"got {loss_prev.shape}")
    rows = (loss_prev, loss_prev2, label_js, part_count, last_selected,
            update_sqnorm, has_loss, has_momentum)
    stacked = _pack(rows, staleness_override, k_total, k_total)
    # Edge e's size in lane 0 of its LANE-wide slice of one (1, E·LANE) row.
    sizes_op = jnp.zeros((num_edges, LANE), jnp.float32).at[:, 0].set(
        jnp.asarray(sizes, jnp.float32)).reshape(1, num_edges * LANE)
    t = jnp.asarray(round_idx, jnp.float32)
    use_ov = 0.0 if staleness_override is None else 1.0
    # Stat lanes start at zero — filled per-edge inside the kernel.
    scal = _scalar_row(t, tau, use_ov, 0.0, 0.0, 0.0, 0.0, 0.0, k_total)

    kernel = functools.partial(_segment_kernel, cfg=cfg, seg=seg)
    probs, scores = pl.pallas_call(
        kernel,
        grid=(num_edges,),
        in_specs=[pl.BlockSpec((NROWS, seg), lambda e: (0, e)),
                  pl.BlockSpec((1, LANE), lambda e: (0, e)),
                  pl.BlockSpec((1, LANE), lambda e: (0, 0))],
        out_specs=[pl.BlockSpec((1, seg), lambda e: (0, e)),
                   pl.BlockSpec((1, seg), lambda e: (0, e))],
        out_shape=[jax.ShapeDtypeStruct((1, k_total), jnp.float32),
                   jax.ShapeDtypeStruct((1, k_total), jnp.float32)],
        interpret=interpret,
    )(stacked, sizes_op, scal)
    return probs.reshape(-1), scores.reshape(-1)


def sharded_score_select(
    loss_prev, loss_prev2, label_js, part_count, last_selected,
    update_sqnorm, has_loss, has_momentum,
    *, round_idx, tau, m: int, key, cfg: HeteRoScoreConfig, mesh,
    axis: str = "clients", staleness_override=None,
    interpret: bool = False, block: Optional[int] = None,
):
    """`fused_score_select` distributed over a client device axis.

    The stacked state shards along clients (shard_map); each device runs the
    two-pass kernel on its shard, then three cross-shard collectives stitch
    the global result: pmin/pmax/psum for the pass-1 statistics, a
    pmax/psum (m, l) merge for the softmax normalizer, and an all_gather of
    the per-shard top-m candidates for the final cut. Returns
    ``(selected_idx, probs, scores)`` like the single-device path.
    """
    from repro.sharding.rules import axis_size
    from jax.sharding import PartitionSpec as P

    ndev = max(axis_size(mesh, axis), 1)
    k = loss_prev.shape[0]
    local_k = -(-k // (ndev * LANE)) * LANE  # LANE-aligned per-device slice
    kpad = local_k * ndev
    rows = (loss_prev, loss_prev2, label_js, part_count, last_selected,
            update_sqnorm, has_loss, has_momentum)
    stacked = _pack(rows, staleness_override, k, kpad)
    gumbel = jax.random.gumbel(key, (k,), jnp.float32)
    gpad = jnp.pad(gumbel, (0, kpad - k)).reshape(1, kpad)

    blk, nblocks, local_pad = _layout(local_k, block)
    assert local_pad == local_k or local_pad > local_k
    t = jnp.asarray(round_idx, jnp.float32)
    use_ov = 0.0 if staleness_override is None else 1.0
    m_local = min(m, local_k)

    def shard_body(stacked_l, gpad_l):
        # Per-shard column offset; global validity limit is K everywhere,
        # but a shard's padding tail must not alias the next shard's ids —
        # clamp the limit to this shard's own extent.
        idx = jax.lax.axis_index(axis)
        off = (idx * local_k).astype(jnp.float32)
        klim = jnp.minimum(off + local_k, float(k))
        if local_pad > local_k:
            stacked_l = jnp.pad(stacked_l, ((0, 0), (0, local_pad - local_k)))
            gpad_l = jnp.pad(gpad_l, ((0, 0), (0, local_pad - local_k)))
        scal0 = _scalar_row(t, tau, use_ov, 0.0, 0.0, 0.0, 1.0, off, klim)
        st = _run_stats(stacked_l, scal0, nblocks=nblocks, block=blk,
                        interpret=interpret)
        lmin = jax.lax.pmin(jnp.min(st[:, ST_LMIN]), axis)
        lmax = jax.lax.pmax(jnp.max(st[:, ST_LMAX]), axis)
        sumsq = jax.lax.psum(jnp.sum(st[:, ST_SUMSQ]), axis)
        nobs = jax.lax.psum(jnp.sum(st[:, ST_NOBS]), axis)
        hmax = jax.lax.pmax(jnp.max(st[:, ST_HMAX]), axis)
        avgsq = sumsq / jnp.maximum(nobs, 1.0)
        hmax = jnp.maximum(hmax, 1.0)
        scal = _scalar_row(t, tau, use_ov, lmin, lmax, avgsq, hmax, off, klim)

        scores_l, e_l, part_l, pert_l = _run_select(
            stacked_l, scal, gpad_l, cfg=cfg, nblocks=nblocks, block=blk,
            interpret=interpret)

        # Cross-shard softmax normalizer merge (flash-attention style).
        m_b = part_l[:, 0]
        l_b = part_l[:, 1]
        mglob = jax.lax.pmax(jnp.max(m_b), axis)
        lglob = jnp.maximum(
            jax.lax.psum(jnp.sum(l_b * jnp.exp(m_b - mglob)), axis), 1e-30)
        scale = jnp.exp(m_b - mglob)
        probs_l = (e_l.reshape(nblocks, blk) * scale[:, None] / lglob
                   ).reshape(-1)[:local_k]
        # Per-shard top-m candidates (any global top-m element is beaten by
        # at most m−1 others, so it survives its shard's cut), then the
        # merge: every shard sees all candidates → identical replicated
        # top-m on every device.
        cval_l, loc = jax.lax.top_k(pert_l.reshape(-1)[:local_k], m_local)
        cidx_l = loc + idx * local_k
        cval_all = jax.lax.all_gather(cval_l, axis).reshape(-1)
        cidx_all = jax.lax.all_gather(cidx_l, axis).reshape(-1)
        _, pos = jax.lax.top_k(cval_all, m)
        selected = cidx_all[pos]
        return selected, probs_l.reshape(1, local_k), \
            scores_l.reshape(-1)[:local_k].reshape(1, local_k)

    selected, probs, scores = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis)),
        out_specs=(P(), P(None, axis), P(None, axis)),
        check_vma=False,
    )(stacked, gpad)
    return selected, probs.reshape(-1)[:k], scores.reshape(-1)[:k]
