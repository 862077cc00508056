"""Plain Mamba-2 language model (arXiv:2405.21060) in float32 jax.numpy.

Each layer: x + Mixer(RMSNorm(x)); the mixer projects to the gate z, the
inputs x, the shared (single-group) B and C and the per-head step dt; runs
a width-4 depthwise causal convolution and SiLU over x and over B, C; then
the selective state-space recurrence

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t (outer) B_t,   y_t = C_t . h_t + D x_t

with one scalar A = -exp(A_log) per head; then RMSNorm(y * SiLU(z)) and the
output projection. Tied embeddings, final RMSNorm, logits over a padded
vocabulary whose padding rows are masked out.

The recurrence is evaluated in its quadratic (state-space dual) form over
the whole sequence, y = (L o C B^T) (dt x) with L_ij = exp(cum_i - cum_j)
for j <= i, a different algorithm from the chunked scan the system runs;
each layer is rematerialized so a sequence of 1024 fits. The projections are
kept separate, as the system keeps them (the published model fuses them into
one in_proj: the same product).

``init`` follows the system's recipe for random weights (truncated-normal
fan-in projections stored in bfloat16, the same key splits), so both start
from the same weights for one seed.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict

import numpy as np

import jax
import jax.numpy as jnp

CONV_K = 4


def _dense(key, shape, dtype):
    std = 1.0 / jnp.sqrt(jnp.maximum(shape[0], 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def dims(cfg: Dict[str, Any]):
    di = cfg["expand"] * cfg["d_model"]
    return di, cfg["d_state"], di // cfg["headdim"]


def init_layer(key, cfg, wdtype=jnp.bfloat16):
    d = cfg["d_model"]
    di, n, nh = dims(cfg)
    kblock = jax.random.split(key, 2)[0]
    kz, kx, kb, kc, kd, ko = jax.random.split(kblock, 6)
    block = {
        "in_z": _dense(kz, (d, di), wdtype), "in_x": _dense(kx, (d, di), wdtype),
        "in_b": _dense(kb, (d, n), wdtype), "in_c": _dense(kc, (d, n), wdtype),
        "in_dt": _dense(kd, (d, nh), wdtype),
        "conv_x_w": _dense(jax.random.fold_in(kx, 1), (CONV_K, di), jnp.float32),
        "conv_x_b": jnp.zeros((di,), jnp.float32),
        "conv_bc_w": _dense(jax.random.fold_in(kb, 1), (CONV_K, 2 * n), jnp.float32),
        "conv_bc_b": jnp.zeros((2 * n,), jnp.float32),
        "A_log": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.full((nh,), -2.0, jnp.float32),
        "norm": jnp.ones((di,), jnp.float32),
        "out_proj": _dense(ko, (di, d), wdtype),
    }
    return {"block": block, "ln": jnp.ones((d,), jnp.float32)}


def init(key, cfg: Dict[str, Any], low: bool = False) -> Dict[str, Any]:
    """Params with the layers as a list (one dict per layer). Projections and
    embeddings are stored in bfloat16, as configured, or in float8 (e4m3)
    for the control (``low``)."""
    wdtype = jnp.float8_e4m3fn if low else jnp.bfloat16
    ke, kl = jax.random.split(key)
    k_tok = jax.random.split(ke)[0]
    tok = jax.random.normal(k_tok, (cfg["padded_vocab"], cfg["d_model"]), jnp.float32)
    tok = tok.astype(wdtype) * 0.02
    layer_keys = jax.random.split(kl, cfg["n_layer"])
    return {
        "embed": {"tok_embed": tok},
        "layers": [init_layer(layer_keys[i], cfg, wdtype) for i in range(cfg["n_layer"])],
        "final_norm": jnp.ones((cfg["d_model"],), jnp.float32),
    }


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def causal_conv(x, w, b):
    """Depthwise causal convolution over the sequence. x (B, S, C), w (K, C)."""
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (CONV_K - 1, 0), (0, 0)))
    return sum(xp[:, k:k + s] * w[k] for k in range(CONV_K)) + b


def ssd(x, dt, a, b, c):
    """Quadratic form of the scan. x (B,S,H,P), dt (B,S,H), a (H,), b/c (B,S,N)."""
    s = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)                          # (B,S,H)
    diff = cum[:, :, None, :] - cum[:, None, :, :]            # (B,i,j,H)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    gram = jnp.einsum("bin,bjn->bij", c, b)
    w = decay * gram[..., None] * dt[:, None, :, :]          # (B,i,j,H)
    return jnp.einsum("bijh,bjhp->bihp", w, x)


def _mm(x, w, mm_dtype):
    """x @ w with both inputs rounded to ``mm_dtype``, accumulated in float32."""
    r = lambda a: a.astype(mm_dtype).astype(jnp.float32)
    return r(x) @ r(w)


def mixer(p, h, cfg, mm_dtype):
    di, n, nh = dims(cfg)
    f = lambda k: p[k].astype(jnp.float32)
    mm = lambda x, k: _mm(x, p[k], mm_dtype)
    z = mm(h, "in_z")
    xs = jax.nn.silu(causal_conv(mm(h, "in_x"), f("conv_x_w"), f("conv_x_b")))
    bc = jnp.concatenate([mm(h, "in_b"), mm(h, "in_c")], axis=-1)
    bc = jax.nn.silu(causal_conv(bc, f("conv_bc_w"), f("conv_bc_b")))
    b, c = bc[..., :n], bc[..., n:]
    dt = jax.nn.softplus(mm(h, "in_dt") + f("dt_bias"))
    a = -jnp.exp(f("A_log"))
    xh = xs.reshape(*xs.shape[:2], nh, cfg["headdim"])
    y = ssd(xh, dt, a, b, c) + f("D")[:, None] * xh
    y = y.reshape(*y.shape[:2], di) * jax.nn.silu(z)
    return mm(rms_norm(y, f("norm"), cfg["norm_eps"]), "out_proj")


def forward(params, tokens, cfg, mm_dtype=jnp.float32):
    """tokens (B, S) -> float32 logits (B, S, padded vocab). ``mm_dtype``
    rounds every matrix product's inputs (float32: exact reference)."""
    x = params["embed"]["tok_embed"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def layer(x, lp):
        return x + mixer(lp["block"], rms_norm(x, lp["ln"], cfg["norm_eps"]), cfg, mm_dtype)

    for lp in params["layers"]:
        x = layer(x, lp)
    x = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    logits = _mm(x, params["embed"]["tok_embed"].T, mm_dtype)
    valid = jnp.arange(logits.shape[-1]) < cfg["vocab_size"]
    return jnp.where(valid, logits, -jnp.inf)


def loss(params, batch, cfg, low: bool = False):
    """Mean next-token cross-entropy, reduced in float32. The control
    (``low``) rounds every matrix product's inputs to float8 (e4m3)."""
    mm_dtype = jnp.float8_e4m3fn if low else jnp.float32
    logits = forward(params, batch["tokens"], cfg, mm_dtype)[:, :-1]
    labels = batch["labels"][:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)



_EVAL: Dict[str, Any] = {}


def metric(params, batch, cfg, low: bool = False, block: int = 4) -> float:
    """The eval the system reports for a language model, exp(-loss) over the
    eval batch, computed ``block`` sequences at a time (equal blocks, so the
    mean of their means is the batch's mean)."""
    key = json.dumps([cfg, low], sort_keys=True)
    if key not in _EVAL:
        _EVAL[key] = jax.jit(functools.partial(loss, cfg=cfg, low=low))
    n = batch["tokens"].shape[0]
    block = min(block, n)
    losses = [float(_EVAL[key](params, {k: v[i:i + block] for k, v in batch.items()}))
              for i in range(0, n, block)]
    return float(np.exp(-np.mean(losses)))
