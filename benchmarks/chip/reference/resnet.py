"""Plain ResNet-18 (CIFAR variant, GroupNorm) in float32 jax.numpy.

He et al., arXiv:1512.03385, with the CIFAR stem (one 3x3 convolution, no
pooling) and GroupNorm(8 groups) in place of BatchNorm, as the HeteRo-Select
paper's federation runs it (arXiv:2508.06692, Sec IV). Stages of widths
(w, 2w, 4w, 8w), two basic blocks each, a 1x1 projection where the shape
changes, global average pooling and a linear head.

``init`` draws the weights from a PRNG key by the same recipe as the system
under test (He-normal convolutions, N(0, 1/fan_in) head, unit GroupNorm
scale, zero biases, the same key splits), so both start from the same
weights for a seed without the reference taking any array from the program.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

STAGES = ((1, 1), (2, 1), (2, 1), (2, 1))  # strides of each stage's two blocks
GROUPS = 8
EPS = 1e-5


def _conv_w(key, k: int, cin: int, cout: int, dtype) -> jax.Array:
    std = (2.0 / (k * k * cin)) ** 0.5
    return (jax.random.normal(key, (k, k, cin, cout), jnp.float32) * std).astype(dtype)


def _norm(c: int, dtype) -> Dict[str, jax.Array]:
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def init(key: jax.Array, cfg: Dict[str, Any], low: bool = False) -> Dict[str, Any]:
    """Weights in float32, or in bfloat16 for the control (``low``)."""
    dtype = jnp.bfloat16 if low else jnp.float32
    w = cfg["d_model"]
    keys = jax.random.split(key, 11)
    params: Dict[str, Any] = {"stem": _conv_w(keys[0], 3, 3, w, dtype), "gn_stem": _norm(w, dtype)}
    cin, i = w, 0
    for stage, strides in enumerate(STAGES):
        cout = w * 2 ** stage
        for stride in strides:
            k1, k2, k3 = jax.random.split(keys[1 + i], 3)
            blk = {"conv1": _conv_w(k1, 3, cin, cout, dtype), "gn1": _norm(cout, dtype),
                   "conv2": _conv_w(k2, 3, cout, cout, dtype), "gn2": _norm(cout, dtype)}
            if stride != 1 or cin != cout:
                blk["proj"] = _conv_w(k3, 1, cin, cout, dtype)
                blk["gn_proj"] = _norm(cout, dtype)
            params[f"block{i}"] = blk
            cin, i = cout, i + 1
    params["fc_w"] = (jax.random.normal(keys[1 + i], (cin, cfg["num_classes"]), jnp.float32)
                      * cin ** -0.5).astype(dtype)
    params["fc_b"] = jnp.zeros((cfg["num_classes"],), dtype)
    return params


def conv(x, w, stride: int = 1):
    return jax.lax.conv_general_dilated(x, w.astype(x.dtype), (stride, stride), "SAME",
                                        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def group_norm(x, p):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, GROUPS, c // GROUPS)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((g - mean) / jnp.sqrt(var + EPS)).reshape(b, h, w, c)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def forward(params, images, dtype=jnp.float32):
    """images (B, H, W, 3) -> logits (B, classes), computed in ``dtype``."""
    x = jax.nn.relu(group_norm(conv(images.astype(dtype), params["stem"]), params["gn_stem"]))
    i = 0
    for strides in STAGES:
        for stride in strides:
            p = params[f"block{i}"]
            y = jax.nn.relu(group_norm(conv(x, p["conv1"], stride), p["gn1"]))
            y = group_norm(conv(y, p["conv2"]), p["gn2"])
            skip = group_norm(conv(x, p["proj"], stride), p["gn_proj"]) if "proj" in p else x
            x = jax.nn.relu(skip + y)
            i += 1
    x = x.mean(axis=(1, 2))
    return x @ params["fc_w"].astype(dtype) + params["fc_b"].astype(dtype)


def loss(params, batch, cfg, low: bool = False):
    """Mean softmax cross-entropy over the batch, reduced in float32. The
    control (``low``) computes the network in bfloat16."""
    logits = forward(params, batch["images"], jnp.bfloat16 if low else jnp.float32)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


@jax.jit
def _hits(params, images, labels):
    return jnp.sum(jnp.argmax(forward(params, images), -1) == labels)


@jax.jit
def _hits_low(params, images, labels):
    return jnp.sum(jnp.argmax(forward(params, images, jnp.bfloat16), -1) == labels)


def metric(params, batch, cfg, low: bool = False, block: int = 1000) -> float:
    """The eval the system reports for a classifier: accuracy over the eval
    batch, ``block`` images at a time; the network computed in bfloat16 for
    the control (``low``)."""
    hits = _hits_low if low else _hits
    images, labels = batch["images"], batch["labels"]
    n = images.shape[0]
    total = sum(int(hits(params, images[i:i + block], labels[i:i + block]))
                for i in range(0, n, block))
    return total / n
