"""The Mamba-2 model against plain references, at a tiny size on the CPU.

``models/mamba2.loss_fn`` and its gradients are compared with the benchmark's
plain reference (``benchmarks/chip/reference/mamba2.py``: the scan in its
whole-sequence quadratic form, a different algorithm from the chunked scan),
and ``_ssd_chunked`` alone with the exact sequential recurrence
(``kernels/ref.ssd_reference``). Both sides compute in float32 under the
``highest`` matmul precision, so what is left between them is the order of
float32 sums; each tolerance says how large that is.

The overflow cases make a chunk's Σ dt·|A| pass 88, where exp(cum_i − cum_j)
above the diagonal is inf in float32: a scan that takes the exp before the
causal mask returns NaN there (inf · 0).
"""

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.ref import ssd_reference
from repro.models import mamba2

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from reference import mamba2 as reference  # noqa: E402

LAYERS, D_MODEL, HEADDIM, D_STATE, CHUNK, SEQ, VOCAB, BATCH = 2, 64, 16, 16, 16, 64, 256, 2

# Relative gap of the mean loss: both sides sum the same float32 terms in a
# different order (chunked vs whole-sequence scan); a few ulps of 1.2e-7.
LOSS_RTOL = 1e-5


def tiny_config():
    cfg = dataclasses.replace(get_config("mamba2-370m"), num_layers=LAYERS, d_model=D_MODEL,
                              vocab_size=VOCAB, ssm_state=D_STATE, ssm_headdim=HEADDIM,
                              ssm_chunk=CHUNK)
    ref_cfg = {"d_model": D_MODEL, "expand": cfg.ssm_expand, "d_state": D_STATE,
               "headdim": HEADDIM, "norm_eps": cfg.norm_eps, "n_layer": LAYERS,
               "vocab_size": VOCAB, "padded_vocab": cfg.padded_vocab}
    return cfg, ref_cfg


def to_reference(params):
    """The program's stacked layers as the reference's list of layers."""
    layers = [jax.tree_util.tree_map(lambda a, i=i: a[i], params["layers"])
              for i in range(LAYERS)]
    return {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"]}


def rel_gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# Each case: (dt_bias, |A|) per head, and the bound on each gradient leaf's
# ||g − g_ref|| / ||g_ref||. A wrong term in the scan reads O(1) there.
INITS = {
    # The model's own initialisation (dt ≈ 0.13, A = -1): float32 round-off
    # through two layers and their backward passes reads ≤ 1.6e-6.
    "default_init": (None, 1e-5),
    # dt ≈ softplus(2) ≈ 2.1 and A = -4 put Σ dt·|A| over one chunk of 16 near
    # 136. The A_log and dt_bias gradients are then sums of decays from
    # e^-8.5 down past float32's smallest number, summed in another order by
    # each side, and read up to 3.8e-4 and 1.4e-5; every other leaf ≤ 2.8e-6.
    "overflow": ((2.0, 4.0), 1e-3),
}


@pytest.fixture(scope="module")
def value_and_grads():
    """The program's and the reference's loss and gradients, jitted once for
    both cases (they differ only in parameter values)."""
    cfg, ref_cfg = tiny_config()
    program = jax.jit(jax.value_and_grad(lambda p, batch: mamba2.loss_fn(cfg, p, batch)))
    ref = jax.jit(jax.value_and_grad(lambda p, batch: reference.loss(p, batch, ref_cfg)))
    return cfg, program, ref


@pytest.mark.parametrize("init", list(INITS))
def test_loss_and_grads_match_reference(init, value_and_grads, monkeypatch):
    # The network's activations are bfloat16 as configured; float32 here, so
    # the comparison sees the scan's arithmetic and not bfloat16 rounding.
    monkeypatch.setattr(mamba2, "DEFAULT_DTYPE", jnp.float32)
    cfg, program, ref = value_and_grads
    override, grad_rtol = INITS[init]
    kp, kt = jax.random.split(jax.random.PRNGKey(11))
    params = mamba2.init_params(kp, cfg)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    if override is not None:
        dt_bias, a_abs = override
        blk = params["layers"]["block"]
        blk["dt_bias"] = jnp.full_like(blk["dt_bias"], dt_bias)
        blk["A_log"] = jnp.full_like(blk["A_log"], math.log(a_abs))
        assert float(jax.nn.softplus(dt_bias)) * a_abs * CHUNK > 88.0
    tokens = jax.random.randint(kt, (BATCH, SEQ), 0, VOCAB)
    batch = {"tokens": tokens, "labels": tokens}

    with jax.default_matmul_precision("highest"):
        loss, grads = program(params, batch)
        ref_loss, ref_grads = ref(to_reference(params), batch)

    assert np.isfinite(float(loss)), "the program's loss is not finite"
    assert float(loss) == pytest.approx(float(ref_loss), rel=LOSS_RTOL)
    ref_grads = {"embed": ref_grads["embed"], "final_norm": ref_grads["final_norm"],
                 "layers": jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                                  *ref_grads["layers"])}
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    ref_leaves = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(leaves) == len(ref_leaves)
    for path, g in leaves:
        name = jax.tree_util.keystr(path)
        assert np.isfinite(np.asarray(g)).all(), name
        assert rel_gap(g, ref_leaves[path]) < grad_rtol, name


def test_chunked_scan_matches_recurrence_past_overflow():
    """dt = 1 and A = -1 over chunks of 256: Σ dt·|A| per chunk is 256."""
    b, s, nh, hp, n, chunk = 1, 512, 2, 8, 8, 256
    kx, kb, kc, ky = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(kx, (b, s, nh, hp))
    b_in = jax.random.normal(kb, (b, s, n))
    c_in = jax.random.normal(kc, (b, s, n))
    dt = jnp.ones((b, s, nh))
    a_neg = -jnp.ones((nh,))
    proj = jax.random.normal(ky, (b, s, nh, hp))

    def objective(scan):
        def f(x, dt, b_in, c_in):
            y, h = scan(x, dt, a_neg, b_in, c_in)
            return jnp.sum(y * proj) + jnp.sum(h)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))

    with jax.default_matmul_precision("highest"):
        y, h = jax.jit(mamba2._ssd_chunked, static_argnums=5)(x, dt, a_neg, b_in, c_in, chunk)
        y_ref, h_ref = ssd_reference(x, dt, a_neg, b_in, c_in)
        _, grads = objective(lambda *a: mamba2._ssd_chunked(*a, chunk))(x, dt, b_in, c_in)
        _, ref_grads = objective(ssd_reference)(x, dt, b_in, c_in)

    # The exact recurrence and the chunked form differ by float32 round-off
    # alone: with a decay of e^-1 a step only the last few dozen steps count,
    # so the gap stays a few ulps (measured ~1e-7).
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(h)).all()
    assert rel_gap(y, y_ref) < 1e-5
    assert rel_gap(h, h_ref) < 1e-5
    for name, g, g_ref in zip(("x", "dt", "b", "c"), grads, ref_grads):
        assert np.isfinite(np.asarray(g)).all(), name
        assert rel_gap(g, g_ref) < 1e-5, name
