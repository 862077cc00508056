"""Batched client-execution engine: all selected clients in one device call.

The sequential reference path (``FedConfig.client_execution="sequential"``)
dispatches one jitted ``local_train`` per selected client — fine for the
paper's 12-client federation, but at cross-device scale (10³–10⁶ clients,
see docs/engine.md §4) per-client Python dispatch dominates wall-clock
and the accelerator idles between visits.

This module stacks the selected clients into struct-of-arrays batches
(leading client axis M) and runs the whole cohort as ONE jitted
``jax.vmap``-over-clients FedProx step:

  * ``stack_client_trees``      — list-of-pytrees → pytree with (M, ...) leaves.
  * ``make_batched_local_train``— vmapped + jitted ``fed.client.local_train``;
    with a multi-device mesh it wraps the vmapped step in ``shard_map`` over
    the 'pod' (stacked-client) axis, reusing ``repro.sharding.rules``
    conventions (params replicated, client axis sharded).
  * ``train_clients_batched``   — drives one round's cohort, optionally in
    fixed-size chunks (bounded memory at M ≫ 10²), and aggregates with the
    fused weighted reduction in ``fed.server`` instead of a Python loop.

Numerics: the batched path computes exactly the same per-client updates as
the sequential path (vmap does not change the math, only the scheduling);
aggregation reassociates the floating-point sum, so results agree to float
tolerance — asserted by tests/test_batched_engine.py.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.fed import server as fed_server
from repro.fed.client import LocalResult, LossFn, local_train
from repro.obs.phases import phase
from repro.sharding import rules
from repro.sharding.rules import MeshAxes, axis_size

BatchedTrainFn = Callable[[Any, Any], LocalResult]


def stack_client_trees(trees: Sequence[Any]) -> Any:
    """[pytree] * M → pytree whose leaves gain a leading (M,) client axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def gather_stacked_batches(data: Any, selected: np.ndarray, steps: int,
                           batch: int, rng: np.random.Generator) -> Any:
    """Stacked (M, steps, batch, ...) batches for the selected clients.

    Prefers the data source's vectorized ``stacked_client_batches`` (the lazy
    large-K generators materialize the whole cohort in one numpy pass);
    otherwise stacks per-client draws in selection order, which consumes the
    host RNG exactly like the sequential path — that is what makes the
    K=12 batched-vs-sequential equivalence test bit-identical on data.
    """
    fn = getattr(data, "stacked_client_batches", None)
    if fn is not None:
        return fn(selected, steps, batch, rng)
    return stack_client_trees(
        [data.client_batches(int(k), steps, batch, rng) for k in selected])


def shard_cohort(stacked_batches: Any, mesh: Mesh,
                 axes: MeshAxes = rules.POD_AXES) -> Any:
    """Place a stacked cohort on the mesh, client axis sharded over 'pod'.

    Reuses ``repro.sharding.rules.batch_specs(client_axis=True)`` so the
    layout matches what the shard_map path of ``make_batched_local_train``
    expects — avoids an implicit all-to-all on entry.
    """
    specs = rules.batch_specs(stacked_batches, mesh, axes, client_axis=True)
    return jax.tree_util.tree_map(
        jax.device_put, stacked_batches, rules.named(mesh, specs))


def make_batched_local_train(
    loss_fn: LossFn,
    *,
    lr: float,
    mu: float,
    mesh: Optional[Mesh] = None,
    axes: Optional[MeshAxes] = None,
    **loss_kw,
) -> BatchedTrainFn:
    """One jitted call training M clients: (params, stacked_batches) → LocalResult.

    ``params`` is the round's global model (shared FedProx anchor, broadcast
    to every client); ``stacked_batches`` has a leading (M,) client axis on
    every leaf. The returned ``LocalResult`` carries (M, ...) params and
    (M,) metadata.

    With ``mesh``/``axes`` naming a 'pod' axis of size > 1 the vmapped step
    runs under ``shard_map``: the client axis is sharded over 'pod'
    (``P(axes.pod)`` on every batch/output leaf — the ``client_axis=True``
    convention of ``repro.sharding.rules``) while params stay replicated.
    M must then be a multiple of the pod-axis size (pad the cohort).
    """
    step = functools.partial(local_train, loss_fn, lr=lr, mu=mu, **loss_kw)
    vmapped = jax.vmap(step, in_axes=(None, 0))
    if mesh is not None and axes is not None and axes.pod is not None \
            and axes.pod in mesh.axis_names and axis_size(mesh, axes.pod) > 1:
        vmapped = jax.shard_map(
            vmapped, mesh=mesh,
            in_specs=(P(), P(axes.pod)),
            out_specs=P(axes.pod),
            check_vma=False,
        )
    return jax.jit(vmapped)


class CohortResult(NamedTuple):
    """One round's cohort outcome (client axis already reduced for params)."""

    avg_params: Any            # fused weighted mean over the M clients
    stacked_params: Optional[Any]  # (M, ...) per-client params (None if chunked)
    mean_loss: jax.Array       # (M,) per-client mean local loss
    update_sqnorm: jax.Array   # (M,) per-client ||Δw||²


def _pad_cohort(stacked_batches: Any, m: int, target: int) -> Any:
    """Pad the client axis to ``target`` by repeating client 0 (weight 0)."""
    def pad(x):
        reps = jnp.broadcast_to(x[:1], (target - m,) + x.shape[1:])
        return jnp.concatenate([x, reps], axis=0)

    return jax.tree_util.tree_map(pad, stacked_batches)


def train_clients_batched(
    batched_train: BatchedTrainFn,
    params: Any,
    stacked_batches: Any,
    *,
    weights: Optional[jax.Array] = None,
    chunk: int = 0,
    pad_to: int = 0,
    keep_client_params: bool = False,
) -> CohortResult:
    """Train one round's cohort and fuse-aggregate its updates.

    ``chunk > 0`` bounds device memory: the cohort runs in ⌈M/chunk⌉ calls of
    a fixed shape (one compile), each chunk's weighted parameter sum folded
    into the running aggregate — the full (M, ...) stacked params never
    materialize. ``weights=None`` is the paper's unweighted FedAvg.

    ``pad_to > 1`` (the mesh's pod-axis size when ``batched_train`` was built
    with one) guarantees every device call sees a client axis divisible by
    it: the chunk size is rounded up to a multiple, and an unchunked cohort
    whose M does not divide is padded with zero-weight repeats.
    """
    m = jax.tree_util.tree_leaves(stacked_batches)[0].shape[0]
    if pad_to and pad_to > 1:
        if chunk:
            chunk = -(-chunk // pad_to) * pad_to
        elif m % pad_to:
            chunk = -(-m // pad_to) * pad_to  # one padded call via chunk path

    if not chunk or (chunk >= m and m % max(pad_to, 1) == 0):
        res = batched_train(params, stacked_batches)
        with phase("aggregate"):
            avg = fed_server.fedavg_fused(res.params, weights)
        return CohortResult(
            avg_params=avg,
            stacked_params=res.params if keep_client_params else None,
            mean_loss=res.mean_loss,
            update_sqnorm=res.update_sqnorm,
        )

    if weights is None:
        w = jnp.full((m,), 1.0 / m, jnp.float32)
    else:
        w = jnp.asarray(weights, jnp.float32)
        w = w / jnp.maximum(jnp.sum(w), 1e-30)
    padded_m = -(-m // chunk) * chunk
    if padded_m != m:
        stacked_batches = _pad_cohort(stacked_batches, m, padded_m)
        w = jnp.concatenate([w, jnp.zeros((padded_m - m,), jnp.float32)])

    acc: Any = None
    losses = []
    sqnorms = []
    for start in range(0, padded_m, chunk):
        sl = jax.tree_util.tree_map(
            lambda x: jax.lax.slice_in_dim(x, start, start + chunk, axis=0),
            stacked_batches,
        )
        res = batched_train(params, sl)
        with phase("aggregate"):
            part = fed_server.weighted_sum_stacked(res.params,
                                                   w[start:start + chunk])
            acc = part if acc is None else jax.tree_util.tree_map(jnp.add, acc, part)
        losses.append(res.mean_loss)
        sqnorms.append(res.update_sqnorm)
    with phase("aggregate"):
        avg = jax.tree_util.tree_map(
            lambda s, p: s.astype(p.dtype), acc,
            jax.tree_util.tree_map(lambda x: x[0], res.params),
        )
    return CohortResult(
        avg_params=avg,
        stacked_params=None,
        mean_loss=jnp.concatenate(losses)[:m],
        update_sqnorm=jnp.concatenate(sqnorms)[:m],
    )
