"""Mixture-of-Experts decoder (Kimi-K2, Grok-1).

Expert parallelism is implemented with ``shard_map`` + ``jax.lax.ragged_dot``
grouped matmuls — **dropless**, sort-based dispatch:

  * activations arrive data-sharded over 'data' and replicated over 'model'
    (the ambient layout after tensor-parallel attention);
  * every device holds a (d_model/FSDP × d_ff/TP)-sharded slice of *all*
    experts, so token→expert routing needs **no all-to-all**: each device
    computes its f-slice of every (token, expert) pair it owns, and a single
    'model'-axis psum combines the slices. FSDP shards are all-gathered per
    layer (standard FSDP schedule).
  * token-expert pairs are sorted by expert id and fed to ``ragged_dot``
    (TPU grouped-matmul), giving exact top-k MoE with zero capacity drops.

This is the TPU-native adaptation discussed in docs/kernels.md §2: expert weights
stay stationary; the collective pattern is (FSDP all-gather + one psum)
instead of the GPU-style all-to-all pipeline. The all-to-all alternative is
evaluated in the §Perf hillclimb.

A dense fallback (no mesh) computes all experts explicitly — used by the
CPU smoke tests (≤4 experts).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.layers import (
    DEFAULT_DTYPE,
    Params,
    cross_entropy,
    dense_init,
    embed_tokens,
    init_embeddings,
    rms_norm,
    scan_layers,
    unembed,
)


def init_moe_ffn(key: jax.Array, cfg: ModelConfig) -> Params:
    kr, kg, ku, kd = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": dense_init(kr, (d, e), dtype=jnp.float32),
        "w_gate": dense_init(kg, (e, d, f), in_axis=1, dtype=DEFAULT_DTYPE),
        "w_up": dense_init(ku, (e, d, f), in_axis=1, dtype=DEFAULT_DTYPE),
        "w_down": dense_init(kd, (e, f, d), in_axis=1, dtype=DEFAULT_DTYPE),
    }


def _route(router: jax.Array, x_flat: jax.Array, k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing. Returns (gates [T,k], experts [T,k] int32, aux_loss)."""
    logits = x_flat.astype(jnp.float32) @ router  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * Σ_e (frac tokens to e) · (mean prob e)
    e = probs.shape[-1]
    sel = jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(jnp.mean(sel, axis=0) * jnp.mean(probs, axis=0))
    return gates.astype(jnp.float32), experts.astype(jnp.int32), aux


def _grouped_ffn(
    xs: jax.Array, group_sizes: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array
) -> jax.Array:
    """SwiGLU through per-expert weights via ragged (grouped) matmuls."""
    g = jax.lax.ragged_dot(xs, wg, group_sizes)
    u = jax.lax.ragged_dot(xs, wu, group_sizes)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(xs.dtype)
    return jax.lax.ragged_dot(h, wd, group_sizes)


def _moe_ffn_local(cfg: ModelConfig, lp: Params, x: jax.Array,
                   *, model_axis: Optional[str], fsdp_axis: Optional[str]) -> Tuple[jax.Array, jax.Array]:
    """Body shared by the shard_map path (axes set) and local path (axes None).

    x: (B_loc, S, d) — the per-device (or full, if no mesh) activation slab.
    Returns (out, aux_loss).
    """
    b, s, d = x.shape
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    x_flat = x.reshape(b * s, d)
    t = b * s

    wg, wu, wd = lp["w_gate"], lp["w_up"], lp["w_down"]
    if fsdp_axis is not None:
        # FSDP: gather the d_model shards back per layer (f stays TP-sharded).
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)

    gates, experts, aux = _route(lp["router"], x_flat, k)

    pair_expert = experts.reshape(t * k)
    pair_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    pair_gate = gates.reshape(t * k)
    order = jnp.argsort(pair_expert)
    sorted_expert = pair_expert[order]
    sorted_token = pair_token[order]
    sorted_gate = pair_gate[order]
    xs = x_flat[sorted_token]
    group_sizes = jnp.bincount(sorted_expert, length=e).astype(jnp.int32)

    ys = _grouped_ffn(xs, group_sizes, wg, wu, wd)  # (T·k, d) — f-slice partial
    ys = ys * sorted_gate[:, None].astype(ys.dtype)
    out = jnp.zeros((t, d), ys.dtype).at[sorted_token].add(ys)
    if model_axis is not None:
        # w_down contracted a TP-sharded f dim ⇒ combine slices. Scatter first
        # (T·d ≪ T·k·d), psum after — see module docstring.
        out = jax.lax.psum(out, model_axis)
        aux = jax.lax.pmean(aux, model_axis)
    return out.reshape(b, s, d), aux


def _moe_ffn_a2a(cfg: ModelConfig, lp: Params, x: jax.Array,
                 *, data_axis: str, model_axis: str,
                 dsz: int) -> Tuple[jax.Array, jax.Array]:
    """§Perf hillclimb path: experts sharded over 'data', token all-to-all.

    Device (i, j) holds experts E_i (E/|data| of them) with f-slice j. Tokens
    (data-sharded over i, replicated over j) are dispatched to their experts'
    owner shards with one all-to-all over 'data', run through ragged_dot
    grouped matmuls, psum'd over 'model' (f contraction) and returned by the
    inverse all-to-all. No per-layer weight gather — the baseline 'gather'
    impl moves E·d·f·2B of weights per layer; this moves 2·T·k·d·2B of
    activations (≈4× less for Kimi-K2 at train_4k, ∞× less at decode).
    Capacity per (src, dst) pair is cf·T_loc·k/|data| with drop-on-overflow.
    """
    # dsz comes in statically from the mesh (shapes below depend on it;
    # lax.axis_size does not exist on older jax).
    b, s, d = x.shape
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    e_loc = e // dsz
    t = b * s
    x_flat = x.reshape(t, d)

    gates, experts, aux = _route(lp["router"], x_flat, k)
    pair_expert = experts.reshape(t * k)
    pair_gate = gates.reshape(t * k)
    pair_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    owner = pair_expert // e_loc                      # destination data-shard
    cap = int(cfg.moe_capacity_factor * t * k / dsz + 0.5)
    # rank of each pair within its destination shard (stable order)
    order = jnp.argsort(owner)
    ranks = jnp.zeros((t * k,), jnp.int32)
    seq = jnp.arange(t * k, dtype=jnp.int32)
    start = jnp.searchsorted(owner[order], jnp.arange(dsz, dtype=jnp.int32))
    rank_sorted = seq - start[owner[order]]
    ranks = ranks.at[order].set(rank_sorted)
    keep = ranks < cap                                 # capacity drop
    slot = owner * cap + jnp.where(keep, ranks, 0)

    send_x = jnp.zeros((dsz * cap, d), x.dtype)
    send_x = send_x.at[slot].add(jnp.where(keep[:, None], x_flat[pair_token], 0))
    send_le = jnp.full((dsz * cap,), e_loc, jnp.int32)  # pad group = e_loc
    send_le = send_le.at[slot].set(
        jnp.where(keep, pair_expert % e_loc, e_loc))

    recv_x = jax.lax.all_to_all(send_x.reshape(dsz, cap, d), data_axis, 0, 0,
                                tiled=False).reshape(dsz * cap, d)
    recv_le = jax.lax.all_to_all(send_le.reshape(dsz, cap), data_axis, 0, 0,
                                 tiled=False).reshape(dsz * cap)

    # grouped matmuls over local experts (pad group e_loc gets zero input)
    sort_r = jnp.argsort(recv_le)
    xs = recv_x[sort_r]
    group_sizes = jnp.bincount(recv_le, length=e_loc + 1).astype(jnp.int32)
    wg = jnp.concatenate([lp["w_gate"], jnp.zeros_like(lp["w_gate"][:1])], 0)
    wu = jnp.concatenate([lp["w_up"], jnp.zeros_like(lp["w_up"][:1])], 0)
    wd = jnp.concatenate([lp["w_down"], jnp.zeros_like(lp["w_down"][:1])], 0)
    ys = _grouped_ffn(xs, group_sizes, wg, wu, wd)     # f-slice partial
    ys = jnp.zeros_like(ys).at[sort_r].set(ys)         # unsort
    ys = jax.lax.psum(ys, model_axis)                  # combine f slices

    back = jax.lax.all_to_all(ys.reshape(dsz, cap, d), data_axis, 0, 0,
                              tiled=False).reshape(dsz * cap, d)
    contrib = back[slot] * (pair_gate * keep)[:, None].astype(back.dtype)
    out = jnp.zeros((t, d), contrib.dtype).at[pair_token].add(contrib)
    aux = jax.lax.pmean(aux, model_axis)
    return out.reshape(b, s, d), aux


def moe_ffn(
    cfg: ModelConfig,
    lp: Params,
    x: jax.Array,
    *,
    mesh=None,
    data_axis: str = "data",
    model_axis: str = "model",
    fsdp: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN sub-layer. With a mesh: shard_map expert-parallel path."""
    if mesh is None:
        out, aux = _moe_ffn_local(cfg, lp, x, model_axis=None, fsdp_axis=None)
        return out, aux

    dsz = dict(zip(mesh.axis_names, mesh.devices.shape)).get(data_axis, 1)
    if (cfg.moe_impl == "a2a" and x.shape[0] % dsz == 0
            and cfg.num_experts % dsz == 0):
        in_specs = (
            {
                "router": P(),
                "w_gate": P(data_axis, None, model_axis),
                "w_up": P(data_axis, None, model_axis),
                "w_down": P(data_axis, model_axis, None),
            },
            P(data_axis, None, None),
        )
        fn = functools.partial(_moe_ffn_a2a, cfg,
                               data_axis=data_axis, model_axis=model_axis,
                               dsz=dsz)
        return jax.shard_map(
            lambda lp_, x_: fn(lp_, x_),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(data_axis, None, None), P()),
            check_vma=False,
        )({k: lp[k] for k in ("router", "w_gate", "w_up", "w_down")}, x)

    # Tokens shard over 'data' only when the batch dim divides it; tiny-batch
    # decode (long_500k: B=1) replicates tokens across 'data' — expert weights
    # stay sharded, which is what actually matters there.
    x_axis = data_axis if x.shape[0] % dsz == 0 else None
    fsdp_axis = data_axis if fsdp else None
    wspec_df = P(None, data_axis if fsdp else None, model_axis)
    wspec_fd = P(None, model_axis, data_axis if fsdp else None)
    in_specs = (
        {
            "router": P(),
            "w_gate": wspec_df,
            "w_up": wspec_df,
            "w_down": wspec_fd,
        },
        P(x_axis, None, None),
    )
    out_specs = (P(x_axis, None, None), P())

    fn = functools.partial(_moe_ffn_local, cfg, model_axis=model_axis, fsdp_axis=fsdp_axis)
    return jax.shard_map(
        lambda lp_, x_: fn(lp_, x_),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )(
        {k: lp[k] for k in ("router", "w_gate", "w_up", "w_down")}, x
    )


# ---------------------------------------------------------------------------
# Full MoE decoder
# ---------------------------------------------------------------------------


def init_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn": attn.init_attention(
            k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
            qkv_bias=cfg.qkv_bias,
        ),
        "moe": init_moe_ffn(k2, cfg),
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
    }


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    ke, kl = jax.random.split(key)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    return {
        "embed": init_embeddings(ke, cfg.padded_vocab, cfg.d_model, cfg.tie_embeddings),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }


AUX_COEF = 0.01


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            mesh=None, remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits, total_aux_loss)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = embed_tokens(params["embed"], tokens).astype(DEFAULT_DTYPE)

    def body(x, positions, lp):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        x = x + attn.attention_block(
            lp["attn"], h, positions,
            rope_theta=cfg.rope_theta, causal=True, window=cfg.sliding_window,
        )
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, aux = moe_ffn(cfg, lp["moe"], h, mesh=mesh)
        return x + y, aux

    if remat:
        body = jax.checkpoint(body)

    def scan_fn(carry, lp):
        x, aux_sum = carry
        x, aux = body(x, positions, lp)
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = scan_layers(scan_fn, (x, jnp.float32(0.0)), params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params["embed"], x, cfg.vocab_size), aux_sum


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], *,
            mesh=None) -> jax.Array:
    logits, aux = forward(cfg, params, batch["tokens"], mesh=mesh, remat=cfg.remat)
    return cross_entropy(logits[:, :-1], batch["labels"][:, 1:]) + AUX_COEF * aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def cache_len(cfg: ModelConfig, max_len: int) -> int:
    return min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, jax.Array]:
    return attn.init_kv_cache(
        cfg.num_layers, batch, cache_len(cfg, max_len),
        cfg.num_kv_heads, cfg.resolved_head_dim,
    )


def decode_step(
    cfg: ModelConfig,
    params: Params,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,
    pos: jax.Array,
    *,
    mesh=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    ring = bool(cfg.sliding_window)
    x = embed_tokens(params["embed"], tokens).astype(DEFAULT_DTYPE)

    def scan_fn(x, inp):
        lp, ck, cv = inp
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        y, ck, cv = attn.decode_attention_block(
            lp["attn"], h, ck, cv, pos, rope_theta=cfg.rope_theta, ring=ring,
        )
        x = x + y
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, _ = moe_ffn(cfg, lp["moe"], h, mesh=mesh)
        return x + y, (ck, cv)

    x, (ck, cv) = scan_layers(scan_fn, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.vocab_size)
    return logits, {"k": ck, "v": cv}
