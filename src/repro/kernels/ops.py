"""Jit'd public wrappers around the Pallas kernels.

These are the entry points a TPU deployment swaps in for the pure-jnp model
paths (models default to jnp so CPU dry-runs/tests never require Mosaic).
On the TPU the kernels are compiled by Mosaic; ``interpret=True`` executes
the kernel bodies on CPU, for tests only — :func:`kernel_interpret` is the
one place that picks between the two.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.scoring import HeteRoScoreConfig
from repro.core.state import ClientState, score_inputs
from repro.kernels import flash_attention as _fa
from repro.kernels import score_select as _ss
from repro.kernels import ssd_scan as _ssd


def kernel_interpret() -> bool:
    """Interpret mode for the default backend: ``True`` on ``cpu`` (tests),
    ``False`` on ``tpu`` (compiled). Any other platform raises — there is
    no silent interpreter fallback on an accelerator."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
        f"the default backend is {platform!r}")


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_mha(q, k, v, *, causal: bool = True, window: int = 0,
              interpret: bool = False):
    """GQA flash attention. q: (B,S,H,D); k,v: (B,T,KVH,D) → (B,S,H,D)."""
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                            interpret=interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_forward(x, dt, a_neg, b_in, c_in, *, chunk: int = 256,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Full SSD: Pallas intra-chunk kernel + jnp cross-chunk recurrence.

    x: (B,S,NH,HP); dt: (B,S,NH) (post-softplus); a_neg: (NH,);
    b/c: (B,S,N). Returns (y (B,S,NH,HP) fp32, h_final (B,NH,HP,N)).
    """
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

    y_intra, states, cumlast = _ssd.ssd_chunk(xc, dtc, a_neg, bc, cc,
                                              interpret=interpret)

    # cross-chunk recurrence + inter-chunk correction (jnp — O(S/chunk))
    chunk_decay = jnp.exp(cumlast)  # (B,NC,NH)

    def step(h, inp):
        st, dec = inp
        h_out = h
        return dec[:, :, None, None] * h + st, h_out

    h_final, h_enter = jax.lax.scan(
        step, jnp.zeros((bsz, nh, hp, n), jnp.float32),
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    h_enter = h_enter.transpose(1, 0, 2, 3, 4)  # (B,NC,NH,HP,N)

    da = dtc * a_neg
    cum = jnp.cumsum(da, axis=2)
    y_inter = jnp.einsum("bcin,bcih,bchpn->bcihp", cc, jnp.exp(cum), h_enter)
    y = (y_intra + y_inter).reshape(bsz, nc * chunk, nh, hp)
    return y[:, :s], h_final


def heterosel_probs(state: ClientState, round_idx, tau,
                    cfg: HeteRoScoreConfig, *, staleness_override=None,
                    interpret: bool = False, block=None):
    """Fused additive scoring + softmax (Eqs 1–12) via Pallas.

    ``score_inputs`` owns the state-field → kernel-argument ordering.
    ``staleness_override`` threads the async clock's (K,) Δ into the Eq-7
    freshness term; ``block`` overrides the VMEM block width.
    """
    return _ss.fused_score_probs(
        *score_inputs(state),
        round_idx=round_idx, tau=tau, cfg=cfg,
        staleness_override=staleness_override, interpret=interpret,
        block=block,
    )


def heterosel_topm(state: ClientState, round_idx, tau, m: int, key,
                   cfg: HeteRoScoreConfig, *, staleness_override=None,
                   interpret: bool = False, block=None):
    """Fused scoring + softmax + Gumbel-top-m selection.

    Returns ``(selected_idx (m,), probs, scores)``. For the same PRNG key
    the selection matches ``sample_clients`` over the jnp probabilities —
    the Gumbel noise is drawn identically and ranking the unnormalized
    logits is ranking the log-probabilities.
    """
    return _ss.fused_score_select(
        *score_inputs(state),
        round_idx=round_idx, tau=tau, m=m, key=key, cfg=cfg,
        staleness_override=staleness_override, interpret=interpret,
        block=block,
    )


def heterosel_probs_segmented(state: ClientState, sizes, *, round_idx, tau,
                              cfg: HeteRoScoreConfig, seg: int,
                              staleness_override=None,
                              interpret: bool = False):
    """Per-edge fused scoring over an edge-major (E·seg,) state in ONE
    kernel launch — the hierarchical engine's inner-selection fast path.

    ``state`` must already be laid out edge-major with ``seg``-aligned
    slices (see ``fed.hierarchy``); ``sizes`` is the (E,) member count of
    each slice. Returns ``(probs, scores)`` in the same layout.
    """
    return _ss.segmented_score_probs(
        *score_inputs(state),
        sizes=sizes, round_idx=round_idx, tau=tau, cfg=cfg, seg=seg,
        staleness_override=staleness_override, interpret=interpret,
    )


def heterosel_topm_sharded(state: ClientState, round_idx, tau, m: int, key,
                           cfg: HeteRoScoreConfig, *, mesh,
                           axis: str = "clients", staleness_override=None,
                           interpret: bool = False, block=None):
    """`heterosel_topm` with state + scoring sharded over a client device
    axis (shard_map + cross-shard collectives). Same return contract."""
    return _ss.sharded_score_select(
        *score_inputs(state),
        round_idx=round_idx, tau=tau, m=m, key=key, cfg=cfg, mesh=mesh,
        axis=axis, staleness_override=staleness_override,
        interpret=interpret, block=block,
    )
