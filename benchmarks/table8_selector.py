"""Table 8 (beyond-paper): selection control-plane latency, K = 10³ … 10⁶.

HeteRo-Select's score → softmax → Gumbel-top-m pipeline is the server's
per-round control plane; at cross-device scale (FedScale-like populations,
K ~ 10⁶) it must run off the (K,) metadata SoA without materializing
per-client f32 temporaries for every score component. This table times one
full selection (scoring + softmax + top-m sampling) per method and K:

  * ``jnp``    — the reference path: ``core.scoring.compute_scores`` (six
                 (K,) f32 component arrays) + softmax + ``sample_clients``.
  * ``fused``  — the multi-block two-pass Pallas kernel
                 (``kernels.ops.heterosel_topm``): stats reduce, then blocks
                 stream through VMEM computing scores, probabilities and the
                 Gumbel-perturbed logits, cut by one XLA top-m.
  * ``sharded``— ``heterosel_topm_sharded``: the same kernel under
                 ``shard_map`` over a client device axis with cross-shard
                 collectives for the normalizer and the final top-m (equals
                 ``fused`` on a single device).

The client state is held in bf16 (``core.state.to_bf16`` — the
``FederatedSpec.compact_state`` layout); the fused kernel consumes the bf16
rows directly and upcasts per block in-register. On CPU the kernel runs in
interpret mode, so the fused timings are NOT meaningful as absolute numbers
there — the table's CPU value is the equivalence check plus the jnp
scaling curve; on a TPU backend the same script times the compiled kernel.

    PYTHONPATH=src python benchmarks/table8_selector.py           # full sweep
    PYTHONPATH=src python benchmarks/table8_selector.py --smoke   # CI guard

CSV columns: name,us_per_select,derived(k;m;match). Machine-readable
record: BENCH_selector.json via the shared emitter (benchmarks/common.py).

Acceptance (ISSUE 6): the full sweep completes K=10⁶ scoring + selection
and the fused cohort matches the jnp cohort for every (K, seed).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

try:  # package-style (benchmarks/run.py) or direct execution from benchmarks/
    from benchmarks.common import emit, emit_bench_json
except ImportError:
    from common import emit, emit_bench_json

from repro.core.scoring import HeteRoScoreConfig, compute_scores
from repro.core.selection import (
    SelectorConfig,
    dynamic_temperature,
    sample_clients,
    selection_probabilities,
)
from repro.core.state import init_client_state, to_bf16, to_f32
from repro.kernels import ops as kernel_ops

CFG = HeteRoScoreConfig()
ROUND = jnp.float32(7.0)


def synthetic_state(k: int, seed: int = 0):
    """A mid-training (K,) metadata SoA: most clients observed, some never."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = init_client_state(k, jax.random.uniform(keys[0], (k,), maxval=0.7))
    observed = jax.random.bernoulli(keys[1], 0.9, (k,))
    loss = jax.random.uniform(keys[2], (k,), minval=0.3, maxval=3.0)
    return state.__class__(
        loss_prev=jnp.where(observed, loss, 0.0),
        loss_prev2=jnp.where(observed, loss * 1.1, 0.0),
        label_js=state.label_js,
        part_count=jnp.where(observed,
                             jax.random.randint(keys[3], (k,), 0, 20), 0),
        last_selected=jnp.where(
            observed, jax.random.randint(keys[4], (k,), 0, 7),
            state.last_selected),
        update_sqnorm=jnp.where(
            observed, jax.random.uniform(keys[5], (k,), maxval=2.0), 0.0),
        has_loss=observed.astype(jnp.float32),
        has_momentum=observed.astype(jnp.float32),
    )


def make_methods(m: int, interpret: bool, sel_cfg: SelectorConfig):
    """name → jitted ``(state, key) -> (m,) sorted selected ids``."""

    @jax.jit
    def jnp_select(state, key):
        scores = compute_scores(state, ROUND, CFG)
        probs = selection_probabilities(scores,
                                        dynamic_temperature(ROUND, sel_cfg))
        mask = sample_clients(key, probs, m)
        return jnp.sort(jnp.flatnonzero(mask, size=m))

    @jax.jit
    def fused_select(state, key):
        sel, _, _ = kernel_ops.heterosel_topm(
            state, ROUND, dynamic_temperature(ROUND, sel_cfg), m, key, CFG,
            interpret=interpret)
        return jnp.sort(sel)

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("clients",))

    @jax.jit
    def sharded_select(state, key):
        sel, _, _ = kernel_ops.heterosel_topm_sharded(
            state, ROUND, dynamic_temperature(ROUND, sel_cfg), m, key, CFG,
            mesh=mesh, interpret=interpret)
        return jnp.sort(sel)

    return {"jnp": jnp_select, "fused": fused_select,
            "sharded": sharded_select}


def time_select(fn, state, key, iters: int) -> float:
    """Mean wall ms per call after one warm-up (compile) call."""
    jax.block_until_ready(fn(state, key))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(state, key)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _state_bytes(state) -> int:
    return sum(int(x.size) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))


def run_sweep(ks, *, m_frac: float, iters: int, interpret: bool,
              smoke: bool) -> dict:
    rows = []
    for k in ks:
        m = max(int(round(m_frac * k)), 1)
        sel_cfg = SelectorConfig(num_selected=m)
        methods = make_methods(m, interpret, sel_cfg)
        state = to_bf16(synthetic_state(k))
        key = jax.random.PRNGKey(k)
        it = max(1, iters // 4) if k >= 100_000 else iters
        ref = np.asarray(methods["jnp"](state, key))
        for name, fn in methods.items():
            sel = np.asarray(fn(state, key))
            match = bool(np.array_equal(np.sort(sel), ref))
            ms = time_select(fn, state, key, it)
            rows.append(dict(method=name, k=k, m=m, ms=ms, match=match,
                             iters=it))
            emit(f"{name}_K{k}", ms * 1e3,
                 {"k": k, "m": m, "match": int(match)})
    # Headline for docs/benchmarks.md: the bf16 SoA compaction factor of the
    # selection state (deterministic, unlike interpret-mode wall times).
    probe = synthetic_state(max(ks))
    compaction = _state_bytes(to_f32(probe)) / _state_bytes(to_bf16(probe))
    return {
        "config": dict(ks=list(ks), m_frac=m_frac, iters=iters,
                       interpret=interpret, backend=jax.default_backend(),
                       devices=jax.device_count(), state_dtype="bfloat16",
                       smoke=smoke),
        "state_compaction": compaction,
        "rows": rows,
    }


def main(quick: bool = True, *, ks=None, m_frac: float = 1e-3,
         iters: int = 4) -> None:
    """Callable from benchmarks/run.py (quick=smoke) or the CLI below."""
    ks = ks or ([1_000, 8_192] if quick
                else [1_000, 10_000, 100_000, 1_000_000])
    interpret = kernel_ops.kernel_interpret()
    payload = run_sweep(ks, m_frac=m_frac, iters=iters,
                        interpret=interpret, smoke=quick)
    emit_bench_json("selector", payload)

    mismatch = [r for r in payload["rows"] if not r["match"]]
    if mismatch:
        raise SystemExit(
            f"REGRESSION: fused/sharded cohort differs from the jnp cohort "
            f"at {[(r['method'], r['k']) for r in mismatch]}")


def _cli() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-K CI guard: fails loudly, finishes in ~1 min")
    ap.add_argument("--ks", type=int, nargs="*", default=None,
                    help="override the K sweep")
    ap.add_argument("--m-frac", type=float, default=1e-3,
                    help="cohort fraction m/K (≥1 client)")
    ap.add_argument("--iters", type=int, default=4)
    args = ap.parse_args()
    main(quick=args.smoke, ks=args.ks, m_frac=args.m_frac, iters=args.iters)


if __name__ == "__main__":
    _cli()
