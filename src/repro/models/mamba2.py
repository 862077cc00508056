"""Mamba-2 (SSD — state-space duality) blocks and decoder [arXiv:2405.21060].

Training/prefill uses the chunked SSD algorithm: quadratic attention-like
computation *within* chunks of length ``ssm_chunk`` plus a sequential state
recurrence *across* chunks (lax.scan). Decode is the O(1) recurrence
``h ← exp(Δ·A)·h + Δ·B⊗x``, which is what makes long_500k native for
SSM/hybrid archs (state size is independent of context length).

TPU sharding adaptation (docs/kernels.md §2): the reference implementation fuses
z/x/B/C/Δ into one ``in_proj``; we keep **separate projections** so the
tensor-parallel 'model' axis shards the head dimension (nh) and inner width
(d_inner = nh·headdim) on clean boundaries — the fused layout would place
split points inside shards and force GSPMD reshards. B/C use a single group
(ngroups=1, per config) and stay replicated. The intra-chunk computation is
the hot spot mirrored by the Pallas kernel in ``repro.kernels.ssd_scan``.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
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

CONV_K = 4  # depthwise causal conv kernel width


def block_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(d_inner, n_state, n_heads)."""
    return cfg.d_inner, cfg.ssm_state, cfg.ssm_heads


def init_block(key: jax.Array, cfg: ModelConfig) -> Params:
    di, n, nh = block_dims(cfg)
    d = cfg.d_model
    kz, kx, kb, kc, kd, ko = jax.random.split(key, 6)
    return {
        "in_z": dense_init(kz, (d, di), dtype=DEFAULT_DTYPE),
        "in_x": dense_init(kx, (d, di), dtype=DEFAULT_DTYPE),
        "in_b": dense_init(kb, (d, n), dtype=DEFAULT_DTYPE),
        "in_c": dense_init(kc, (d, n), dtype=DEFAULT_DTYPE),
        "in_dt": dense_init(kd, (d, nh), dtype=DEFAULT_DTYPE),
        "conv_x_w": dense_init(jax.random.fold_in(kx, 1), (CONV_K, di), dtype=jnp.float32),
        "conv_x_b": jnp.zeros((di,), jnp.float32),
        "conv_bc_w": dense_init(jax.random.fold_in(kb, 1), (CONV_K, 2 * n), dtype=jnp.float32),
        "conv_bc_b": jnp.zeros((2 * n,), jnp.float32),
        "A_log": jnp.zeros((nh,), jnp.float32),  # A = -exp(A_log) = -1
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.full((nh,), -2.0, jnp.float32),  # softplus ≈ 0.12
        "norm": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ko, (di, d), dtype=DEFAULT_DTYPE),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv as K shifted adds. x: (B,S,C); w: (K,C)."""
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (CONV_K - 1, 0), (0, 0)))
    out = jnp.zeros(x.shape, jnp.float32)
    for k in range(CONV_K):
        out = out + xp[:, k : k + s].astype(jnp.float32) * w[k]
    return out + b


def _ssd_chunked(
    x: jax.Array,   # (B,S,nh,hp) fp32
    dt: jax.Array,  # (B,S,nh) fp32, post-softplus
    a_neg: jax.Array,  # (nh,) fp32, A = -exp(A_log)
    b_in: jax.Array,   # (B,S,N) fp32
    c_in: jax.Array,   # (B,S,N) fp32
    chunk: int,
    h0: jax.Array | None = None,  # (B,nh,hp,N) initial state
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y (B,S,nh,hp), final_state (B,nh,hp,N)).

    Its ops carry the name scope ``mamba2.ssd`` (sub-scopes ``intra``,
    ``states``, ``scan``, ``inter``), so a device trace can tell the scan's
    time from the rest of the step; see docs/observability.md.
    """
    with jax.named_scope("mamba2.ssd"):
        bsz, s, nh, hp = x.shape
        n = b_in.shape[-1]
        nc = -(-s // chunk)
        pad = nc * chunk - s
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
            c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))
        xc = x.reshape(bsz, nc, chunk, nh, hp)
        dtc = dt.reshape(bsz, nc, chunk, nh)
        bc = b_in.reshape(bsz, nc, chunk, n)
        cc = c_in.reshape(bsz, nc, chunk, n)

        da = dtc * a_neg  # (B,nc,cl,nh) — log-decay increments (≤0)
        cum = jnp.cumsum(da, axis=2)  # inclusive cumulative log decay

        # Intra-chunk (attention-like, causal with decay weights).
        #   W[b,c,i,j,h] = exp(cum_i − cum_j) · dt_j · (C_i · B_j)   for j ≤ i
        # The causal mask is applied to the log-decay (a masked segment sum)
        # before the exp: above the diagonal cum_i − cum_j ≥ 0 and grows with
        # the chunk's Σ dt·|A|, which past ≈ 88 overflows float32 to inf, and
        # inf · 0 would be NaN in the output and the gradient.
        with jax.named_scope("intra"):
            scores = jnp.einsum("bcin,bcjn->bcij", cc, bc)  # (B,nc,cl,cl)
            idx = jnp.arange(chunk)
            causal = (idx[:, None] >= idx[None, :])[None, None, :, :, None]
            segsum = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,i,j,h)
            decay = jnp.exp(jnp.where(causal, segsum, -jnp.inf))
            w = scores[..., None] * decay * dtc[:, :, None, :, :]
            y_intra = jnp.einsum("bcijh,bcjhp->bcihp", w, xc)

        # Per-chunk terminal states:  state[b,c,h,p,n] = Σ_j e^{cum_last−cum_j}·dt_j·x_j⊗B_j
        with jax.named_scope("states"):
            decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,cl,nh)
            wstate = decay_to_end * dtc
            states = jnp.einsum("bcjh,bcjhp,bcjn->bchpn", wstate, xc, bc)

        # Cross-chunk recurrence.
        with jax.named_scope("scan"):
            chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,nh)
            h_init = jnp.zeros((bsz, nh, hp, n), jnp.float32) if h0 is None else h0

            def step(h, inp):
                st, dec = inp  # (B,nh,hp,n), (B,nh)
                h_out = h  # state *entering* this chunk
                h_new = dec[:, :, None, None] * h + st
                return h_new, h_out

            hs_in = (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2))
            h_final, h_enter = jax.lax.scan(step, h_init, hs_in)
            h_enter = h_enter.transpose(1, 0, 2, 3, 4)  # (B,nc,nh,hp,n)

        # Inter-chunk contribution: C_i · (e^{cum_i} · H_enter)
        with jax.named_scope("inter"):
            y_inter = jnp.einsum("bcin,bcih,bchpn->bcihp", cc, jnp.exp(cum), h_enter)
            y = (y_intra + y_inter).reshape(bsz, nc * chunk, nh, hp)
        return y[:, :s], h_final


def block_forward(
    cfg: ModelConfig, lp: Params, x: jax.Array, *, h0=None, return_state: bool = False
):
    """Full Mamba2 block: projections → conv → SSD → gated norm → out_proj."""
    di, n, nh = block_dims(cfg)
    hp = cfg.ssm_headdim
    z = jnp.einsum("bsd,de->bse", x, lp["in_z"])
    xs = jnp.einsum("bsd,de->bse", x, lp["in_x"])
    bc = jnp.concatenate(
        [jnp.einsum("bsd,dn->bsn", x, lp["in_b"]), jnp.einsum("bsd,dn->bsn", x, lp["in_c"])],
        axis=-1,
    )
    dt = jnp.einsum("bsd,dh->bsh", x, lp["in_dt"])
    xs = jax.nn.silu(_causal_conv(xs, lp["conv_x_w"], lp["conv_x_b"]))
    bc = jax.nn.silu(_causal_conv(bc, lp["conv_bc_w"], lp["conv_bc_b"]))
    b_in, c_in = jnp.split(bc, 2, axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    a_neg = -jnp.exp(lp["A_log"])
    xh = xs.reshape(*xs.shape[:2], nh, hp)
    y, h_final = _ssd_chunked(xh, dt, a_neg, b_in, c_in, cfg.ssm_chunk, h0=h0)
    y = y + lp["D"][:, None] * xh  # skip
    y = y.reshape(*y.shape[:2], di)
    y = rms_norm(y.astype(DEFAULT_DTYPE) * jax.nn.silu(z), lp["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, lp["out_proj"])
    if return_state:
        return out, h_final
    return out


def block_decode(
    cfg: ModelConfig, lp: Params, x: jax.Array,
    conv_x_state: jax.Array, conv_bc_state: jax.Array, ssm_state: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-token recurrence. x: (B,1,d).

    States: conv_x (B,K−1,di), conv_bc (B,K−1,2n), ssm (B,nh,hp,N).
    """
    di, n, nh = block_dims(cfg)
    hp = cfg.ssm_headdim
    z = jnp.einsum("bsd,de->bse", x, lp["in_z"])
    xs = jnp.einsum("bsd,de->bse", x, lp["in_x"])[:, 0]
    bc = jnp.concatenate(
        [jnp.einsum("bsd,dn->bsn", x, lp["in_b"]), jnp.einsum("bsd,dn->bsn", x, lp["in_c"])],
        axis=-1,
    )[:, 0]
    dt = jnp.einsum("bsd,dh->bsh", x, lp["in_dt"])[:, 0]

    def conv_step(state, cur, w, b):
        window = jnp.concatenate([state, cur[:, None, :]], axis=1)  # (B,K,C)
        out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w) + b
        return jax.nn.silu(out), window[:, 1:]

    xs_f, new_conv_x = conv_step(conv_x_state, xs, lp["conv_x_w"], lp["conv_x_b"])
    bc_f, new_conv_bc = conv_step(conv_bc_state, bc, lp["conv_bc_w"], lp["conv_bc_b"])
    b_in, c_in = jnp.split(bc_f, 2, axis=-1)
    dt1 = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])  # (B,nh)
    a_neg = -jnp.exp(lp["A_log"])
    dec = jnp.exp(dt1 * a_neg)  # (B,nh)
    xh = xs_f.reshape(-1, nh, hp)
    h_new = (
        dec[:, :, None, None] * ssm_state
        + jnp.einsum("bh,bhp,bn->bhpn", dt1, xh, b_in)
    )
    y = jnp.einsum("bn,bhpn->bhp", c_in, h_new) + lp["D"][:, None] * xh
    y = y.reshape(-1, 1, di)
    y = rms_norm(y.astype(DEFAULT_DTYPE) * jax.nn.silu(z), lp["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, lp["out_proj"])
    return out, new_conv_x, new_conv_bc, h_new


# ---------------------------------------------------------------------------
# Full decoder
# ---------------------------------------------------------------------------


def init_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    kb = jax.random.split(key, 2)
    return {
        "block": init_block(kb[0], cfg),
        "ln": jnp.ones((cfg.d_model,), jnp.float32),
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


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            remat: bool = True) -> jax.Array:
    x = embed_tokens(params["embed"], tokens).astype(DEFAULT_DTYPE)

    def body(x, lp):
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        return x + block_forward(cfg, lp["block"], h)

    if remat:
        body = jax.checkpoint(body)

    def scan_fn(carry, lp):
        return body(carry, lp), None

    x, _ = scan_layers(scan_fn, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params["embed"], x, cfg.vocab_size)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
    logits = forward(cfg, params, batch["tokens"], remat=cfg.remat)
    return cross_entropy(logits[:, :-1], batch["labels"][:, 1:])


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, jax.Array]:
    """SSM cache is O(1) in context length (max_len unused — by design)."""
    del max_len
    di, n, nh = block_dims(cfg)
    return {
        "conv_x": jnp.zeros((cfg.num_layers, batch, CONV_K - 1, di), DEFAULT_DTYPE),
        "conv_bc": jnp.zeros((cfg.num_layers, batch, CONV_K - 1, 2 * n), DEFAULT_DTYPE),
        "ssm": jnp.zeros((cfg.num_layers, batch, nh, cfg.ssm_headdim, n), jnp.float32),
    }


def decode_step(
    cfg: ModelConfig,
    params: Params,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,
    pos: jax.Array,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    del pos  # recurrent state carries position implicitly
    x = embed_tokens(params["embed"], tokens).astype(DEFAULT_DTYPE)

    def scan_fn(x, inp):
        lp, cx, cbc, ss = inp
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        y, cx, cbc, ss = block_decode(cfg, lp["block"], h, cx, cbc, ss)
        return x + y, (cx, cbc, ss)

    x, (cx, cbc, ss) = scan_layers(
        scan_fn, x, (params["layers"], cache["conv_x"], cache["conv_bc"], cache["ssm"])
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.vocab_size)
    return logits, {"conv_x": cx, "conv_bc": cbc, "ssm": ss}
