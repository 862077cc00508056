"""Server-side aggregation (paper Algorithm 1, line 26) + beyond-paper extras.

The paper aggregates with unweighted FedAvg over the selected subset:
    w_t ← (1/m) Σ_{k∈S_t} w_t^k
``fedavg`` implements that; ``fedavg_weighted`` (|D_k|-weighted, the original
McMahan form) and ``ServerMomentum`` (FedAvgM) are provided as optional
aggregators and evaluated in EXPERIMENTS.md §Beyond-paper.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp


def fedavg(client_params: Sequence[Any]) -> Any:
    """Unweighted mean of client parameter pytrees."""
    n = float(len(client_params))
    return jax.tree_util.tree_map(
        lambda *xs: (sum(x.astype(jnp.float32) for x in xs) / n).astype(xs[0].dtype),
        *client_params,
    )


def fedavg_weighted(client_params: Sequence[Any], weights: Sequence[float]) -> Any:
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    return jax.tree_util.tree_map(
        lambda *xs: sum(wi * x.astype(jnp.float32) for wi, x in zip(w, xs)).astype(xs[0].dtype),
        *client_params,
    )


def weighted_sum_stacked(stacked_params: Any, weights: jax.Array) -> Any:
    """Σ_m w_m · x_m over the leading client axis — one fused reduction per leaf.

    The contraction (``tensordot`` over axis 0) is a single XLA reduce per
    parameter leaf, replacing the O(M) Python accumulation of the sequential
    path. Leaves come back float32 (callers cast once at the end); weights
    are used as given (callers normalize).
    """
    w = jnp.asarray(weights, jnp.float32)
    return jax.tree_util.tree_map(
        lambda x: jnp.tensordot(w, x.astype(jnp.float32), axes=1), stacked_params
    )


def fedavg_fused(stacked_params: Any, weights: Optional[jax.Array] = None) -> Any:
    """Weighted FedAvg over a leading (M,) client axis as fused reductions.

    ``weights=None`` → the paper's unweighted mean (Algorithm 1 line 26);
    otherwise weights are normalized to sum to 1. Output leaves keep the
    input dtype. This is the batched engine's aggregation step — see
    docs/engine.md §3.
    """
    m = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if weights is None:
        w = jnp.full((m,), 1.0 / m, jnp.float32)
    else:
        w = jnp.asarray(weights, jnp.float32)
        w = w / jnp.maximum(jnp.sum(w), 1e-30)
    summed = weighted_sum_stacked(stacked_params, w)
    return jax.tree_util.tree_map(
        lambda s, x: s.astype(x.dtype), summed, stacked_params
    )


def params_delta_f32(new_params: Any, anchor: Any) -> Any:
    """Δ = new − anchor, accumulated in f32 regardless of param dtype.

    The one delta convention shared by everything that ships updates as
    anchor-relative deltas — the async engine's per-client deltas, the
    hierarchical engine's per-edge deltas, and ``BufferedAggregator``'s
    sync fallback. ``apply_weighted_deltas`` below is the inverse step.
    """
    return jax.tree_util.tree_map(
        lambda a, g: a.astype(jnp.float32) - g.astype(jnp.float32),
        new_params, anchor)


def apply_weighted_deltas(global_params: Any, deltas: Sequence[Any],
                          weights: jax.Array, server_lr: float = 1.0) -> Any:
    """w ← w + η_s · Σ_i w̄_i Δ_i — the buffered-async server step.

    ``deltas`` are per-update parameter deltas Δ_i = w_i − w_anchor(i), each
    relative to the global model version the producing client trained on (so
    stale arrivals apply cleanly to a newer global). Weights are normalized
    to sum to 1 here; ``fed.async_engine.BufferedAggregator`` computes them
    as polynomial staleness discounts. Accumulation runs in f32, output
    leaves keep the param dtype. With uniform weights, zero staleness and
    η_s = 1 this reduces to FedAvg up to float reassociation.

    This is also the hierarchical cloud stage (``fed.hierarchy``): there the
    deltas are per-*edge* aggregates relative to the dispatch anchor,
    weighted by edge cohort size (× the FedBuff staleness discount when a
    straggler edge arrives late in async mode).
    """
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-30)

    def upd(g, *ds):
        s = sum(wi * d.astype(jnp.float32) for wi, d in zip(w, ds))
        return (g.astype(jnp.float32) + server_lr * s).astype(g.dtype)

    return jax.tree_util.tree_map(upd, global_params, *deltas)


def combine_edges(global_params: Any, edge_params: Sequence[Any],
                  weights: Sequence[float]) -> Any:
    """The synchronous hierarchical cloud stage: this round's edge
    aggregates as size-weighted deltas against the global model
    (``apply_weighted_deltas``). A single edge aggregate is taken as it is,
    bitwise — which is what keeps the E = 1 hierarchy equal to the flat run
    (no f32 round trip through the delta form); none leaves the model as it
    was."""
    if len(edge_params) <= 1:
        return edge_params[0] if edge_params else global_params
    deltas = [params_delta_f32(p, global_params) for p in edge_params]
    return apply_weighted_deltas(global_params, deltas,
                                 jnp.asarray(weights, jnp.float32))


def fedavg_stacked(stacked_params: Any, axis_name: Optional[str] = None) -> Any:
    """FedAvg over a leading client axis (the multi-pod 'pod'-axis path).

    With ``axis_name`` set this is a cross-pod ``pmean`` inside shard_map;
    otherwise a plain mean over axis 0 of stacked client params.
    """
    if axis_name is not None:
        return jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, axis_name), stacked_params)
    return jax.tree_util.tree_map(
        lambda x: jnp.mean(x.astype(jnp.float32), axis=0).astype(x.dtype), stacked_params
    )


@dataclasses.dataclass
class ServerMomentum:
    """FedAvgM: w_t = w_{t-1} − v_t,  v_t = β v_{t-1} + (w_{t-1} − w̄_t).

    Beyond-paper aggregator — damps the round-to-round oscillation that the
    paper measures as 'stability drop'.
    """

    beta: float = 0.9
    velocity: Any = None

    def aggregate(self, prev_global: Any, client_params: Sequence[Any]) -> Any:
        return self.apply(prev_global, fedavg(client_params))

    def aggregate_stacked(self, prev_global: Any, stacked_params: Any,
                          weights: Optional[jax.Array] = None) -> Any:
        """Momentum over the batched engine's (M, ...) client stack."""
        return self.apply(prev_global, fedavg_fused(stacked_params, weights))

    def apply(self, prev_global: Any, avg: Any) -> Any:
        delta = jax.tree_util.tree_map(
            lambda p, a: p.astype(jnp.float32) - a.astype(jnp.float32), prev_global, avg
        )
        if self.velocity is None:
            self.velocity = delta
        else:
            self.velocity = jax.tree_util.tree_map(
                lambda v, d: self.beta * v + d, self.velocity, delta
            )
        return jax.tree_util.tree_map(
            lambda p, v: (p.astype(jnp.float32) - v).astype(p.dtype),
            prev_global, self.velocity,
        )
