"""Sharding rules: params / batch / cache PartitionSpecs per (arch × mesh).

Policy (docs/kernels.md §2):
  * 'model' axis — tensor parallelism: attention heads (or head_dim when the
    head count does not divide the axis, e.g. qwen2's 14 heads), d_ff, vocab,
    MoE d_ff slices, Mamba2 inner width / SSD heads.
  * 'data'  axis — batch data-parallelism; additionally FSDP parameter
    sharding when a replica of (params + FedProx anchor) would not fit
    HBM with model-axis sharding alone (llama3-405b, kimi-k2, grok-1,
    llama-3.2-vision-90b).
  * 'pod'   axis — concurrent federated clients (stacked client axis). The
    batched client-execution engine (fed.batched) shards its cohort's
    leading client axis over 'pod' — ``batch_specs(..., client_axis=True)``
    / ``POD_AXES`` are its conventions.

Every rule degrades gracefully: a dim shards on an axis only when divisible,
otherwise the next candidate dim is tried, otherwise it replicates. That is
not a cop-out — it is what production frameworks do (replicated KV heads in
GQA are standard), and the roofline table quantifies the cost.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# FSDP threshold: params+anchor in bf16 with model-axis-only sharding must
# fit in half of a v5e's 16 GB HBM (leave room for activations/caches).
FSDP_BYTES_THRESHOLD = 4 * (1 << 30)  # per-chip param bytes before FSDP


def needs_fsdp(cfg: ModelConfig, model_axis_size: int) -> bool:
    per_chip = 2 * cfg.param_count() * 2 / max(model_axis_size, 1)  # params+anchor bf16
    return per_chip > FSDP_BYTES_THRESHOLD


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    data: str = "data"
    model: str = "model"
    pod: Optional[str] = None  # set on the multi-pod mesh


# Axis naming used by the batched client-execution engine (fed.batched):
# the stacked-cohort client axis lives on 'pod'.
POD_AXES = MeshAxes(pod="pod")


def axis_size(mesh: Mesh, name: Optional[str]) -> int:
    """Size of a mesh axis; 1 for ``None``, 0 when absent from the mesh.

    0 makes every ``_div`` check fail, so rules never assign an axis the
    mesh does not have — e.g. the batched-cohort engine runs on a pod-only
    mesh with no 'data'/'model' axes and batch dims simply replicate.
    """
    if not name:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 0)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _pick(shape, candidates, axis_name, axis_sz):
    """First candidate dim divisible by the axis gets it; returns spec list."""
    spec = [None] * len(shape)
    for dim in candidates:
        if _div(shape[dim], axis_sz):
            spec[dim] = axis_name
            return spec
    return spec


def _merge(a, b):
    return tuple(x if x is not None else y for x, y in zip(a, b))


def leaf_spec(
    name: str,
    shape: tuple,
    cfg: ModelConfig,
    axes: MeshAxes,
    dsz: int,
    msz: int,
    fsdp: bool,
) -> P:
    """PartitionSpec for one named parameter leaf (layer-stacked dims lead)."""
    nd = len(shape)

    def base(model_cands, fsdp_cands=()):
        spec = _pick(shape, [nd + c if c < 0 else c for c in model_cands], axes.model, msz)
        if fsdp:
            fspec = _pick(shape, [nd + c if c < 0 else c for c in fsdp_cands], axes.data, dsz)
            # avoid double-assigning a dim
            fspec = [f if s is None else None for f, s in zip(fspec, spec)]
            spec = [s if s is not None else f for s, f in zip(spec, fspec)]
        return P(*spec)

    # --- embeddings / heads ---
    if name == "tok_embed":        # (V, d)
        return base([0], [1])
    if name in ("unembed", "head"):  # (d, V)
        return base([1], [0])
    # --- attention ---
    # Heads-dim only: falling back to head_dim would shard the QK/PV
    # contraction and all-reduce S×T score matrices every chunk — the
    # dry-run roofline measured this at ~30 GB/layer for qwen2. Archs whose
    # head count doesn't divide the axis (qwen2 14H, minicpm 36H) run
    # attention replicated on 'model' instead (recorded in EXPERIMENTS.md).
    if name == "wq":               # (..., d, H, hd)
        return base([-2], [-3])
    if name in ("wk", "wv"):       # (..., d, KVH, hd)
        return base([-2], [-3])
    if name == "wo":               # (..., H, hd, d)
        return base([-3], [-1])
    if name in ("bq", "bk", "bv"):  # (..., H, hd)
        return base([-2])
    # --- dense MLP vs MoE experts (ndim disambiguates) ---
    if name in ("w_gate", "w_up"):
        if cfg.family == "moe" and nd >= 4:  # (L, E, d, f)
            if cfg.moe_impl == "a2a":        # experts over data, f over model
                spec = [None] * nd
                if _div(shape[-3], dsz):
                    spec[-3] = axes.data
                if _div(shape[-1], msz):
                    spec[-1] = axes.model
                return P(*spec)
            return base([-1], [-2])
        return base([-1], [-2])              # (L, d, f)
    if name == "w_down":
        if cfg.family == "moe" and nd >= 4:  # (L, E, f, d)
            if cfg.moe_impl == "a2a":
                spec = [None] * nd
                if _div(shape[-3], dsz):
                    spec[-3] = axes.data
                if _div(shape[-2], msz):
                    spec[-2] = axes.model
                return P(*spec)
            return base([-2], [-1])
        return base([-2], [-1])              # (L, f, d)
    if name == "router":           # (L, d, E) — replicated (shard_map reads it whole)
        return P()
    # --- mamba2 ---
    if name in ("in_z", "in_x"):   # (..., d, di)
        return base([-1], [-2])
    if name in ("in_b", "in_c"):   # (..., d, n)
        return base([], [-2])
    if name == "in_dt":            # (..., d, nh)
        return base([-1], [-2])
    if name in ("conv_x_w", "conv_x_b", "norm"):  # (..., K, di) / (..., di)
        return base([-1])
    if name == "out_proj":         # (..., di, d)
        return base([-2], [-1])
    # everything else (norms, gates, biases, A_log, D, dt_bias, conv_bc, fc,
    # resnet convs, mask_embed) — replicated
    return P()


def param_specs(params_shape: Any, cfg: ModelConfig, mesh: Mesh,
                axes: MeshAxes = MeshAxes(), *, client_axis: bool = False,
                fsdp: Optional[bool] = None) -> Any:
    """Spec pytree matching ``params_shape`` (an eval_shape / params pytree).

    ``client_axis=True`` prepends the stacked-client 'pod' dim to every leaf.
    ``fsdp`` overrides the size heuristic (the dry-run probe pins it to the
    full-depth decision so reduced-depth probes shard identically per layer).
    """
    dsz = axis_size(mesh, axes.data)
    msz = axis_size(mesh, axes.model)
    if fsdp is None:
        fsdp = needs_fsdp(cfg, msz)

    def one(path, leaf):
        name = None
        for p in reversed(path):
            k = getattr(p, "key", None)
            if isinstance(k, str):
                name = k
                break
        shape = leaf.shape
        if client_axis:
            shape = shape[1:]
        spec = leaf_spec(name or "", tuple(shape), cfg, axes, dsz, msz, fsdp)
        if client_axis:
            spec = P(axes.pod, *spec)
        return spec

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def batch_specs(batch_shape: Any, mesh: Mesh, axes: MeshAxes = MeshAxes(),
                *, client_axis: bool = False) -> Any:
    """Batch dims shard over 'data' when divisible (B=1 long-context stays
    replicated; its KV cache shards over sequence instead — see cache_specs)."""
    dsz = axis_size(mesh, axes.data)

    def one(leaf):
        shape = leaf.shape[1:] if client_axis else leaf.shape
        spec = [None] * len(shape)
        if len(shape) >= 1 and _div(shape[0], dsz):
            spec[0] = axes.data
        spec = P(axes.pod, *spec) if client_axis else P(*spec)
        return spec

    return jax.tree_util.tree_map(one, batch_shape)


def cache_specs(cache_shape: Any, cfg: ModelConfig, mesh: Mesh,
                axes: MeshAxes = MeshAxes()) -> Any:
    """KV/state cache specs: [L, B, T, KVH, hd] / [L, B, ...] layouts.

    Batch shards over 'data' when divisible; otherwise the *time* dim takes
    'data' (sequence-sharded KV for global_batch=1 long-context decode).
    Heads (KVH / nh) shard over 'model' when divisible, else head_dim.
    """
    dsz = axis_size(mesh, axes.data)
    msz = axis_size(mesh, axes.model)

    def one(path, leaf):
        name = None
        for p in reversed(path):
            k = getattr(p, "key", None)
            if isinstance(k, str):
                name = k
                break
        shape = leaf.shape
        nd = len(shape)
        spec = [None] * nd
        if name in ("k", "v", "attn_k", "attn_v", "xk", "xv"):
            # (..., B, T, KVH, hd)
            bdim, tdim, hdim, ddim = nd - 4, nd - 3, nd - 2, nd - 1
            if _div(shape[bdim], dsz):
                spec[bdim] = axes.data
            elif _div(shape[tdim], dsz):
                spec[tdim] = axes.data
            if _div(shape[hdim], msz):
                spec[hdim] = axes.model
            elif spec[tdim] is None and _div(shape[tdim], msz):
                # GQA with KVH < |model|: sequence-shard the cache instead of
                # head_dim-sharding it. head_dim sharding cannot survive the
                # KVH->H broadcast, so GSPMD all-gathers the whole cache every
                # layer (measured: 1.9 GB/layer fp32 on kimi decode_32k --
                # Perf pair 2 iteration 2). Sequence sharding costs only a
                # [B,H,1] max/sum all-reduce in the softmax.
                spec[tdim] = axes.model
            elif _div(shape[ddim], msz):
                spec[ddim] = axes.model
        elif name == "ssm":
            # (..., B, nh, hp, n)
            bdim, hdim = nd - 4, nd - 3
            if _div(shape[bdim], dsz):
                spec[bdim] = axes.data
            if _div(shape[hdim], msz):
                spec[hdim] = axes.model
        elif name in ("conv_x", "super_conv_x", "tail_conv_x"):
            # (..., B, K-1, di)
            bdim, cdim = nd - 3, nd - 1
            if _div(shape[bdim], dsz):
                spec[bdim] = axes.data
            if _div(shape[cdim], msz):
                spec[cdim] = axes.model
        else:  # conv_bc etc: (..., B, K-1, 2n) — batch only
            bdim = nd - 3
            if nd >= 3 and _div(shape[bdim], dsz):
                spec[bdim] = axes.data
        return P(*spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
