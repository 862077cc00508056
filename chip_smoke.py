"""Smoke test of the federated round on a TPU chip, through the launcher.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the cross-chip paths only

Phases (one process; it alone holds the chip):

  (a) device   — platform, device kind and count; anything but 'tpu' fails.
  (b) training — ``repro.launch.train.main`` for 3 rounds of the paper's
                 federation at full width (ResNet-18, d_model=64, 32×32×3,
                 K=12, M=6, Dirichlet α=0.1, FedProx μ=0.1, HeteRo-Select,
                 batched executor): finite losses, metric in [0, 1],
                 selection counts summing to M × rounds.
  (c) executor — the same 2 rounds with ``--client-execution sequential``:
                 identical cohorts, accuracy within docs/engine.md §3's 1e-2.
  (d) kernel   — 2 rounds with ``--selector heterosel_pallas`` (same cohorts
                 as (b)); the compiled selection step holds a
                 ``tpu_custom_call``; one K=10⁶, m=256 bf16-state selection
                 against the jnp ``heterosel_select`` (probabilities within
                 rtol 1e-5, identical cohort).

With ``--chips 4`` only the cross-chip paths run, each against its
single-device counterpart on device 0: one federated round on a 4-device
'pod' mesh (M=8), and ``sharded_score_select`` at K=10⁶ over 4 devices.

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``; it is printed only when every phase passed. The
compilation cache follows ``JAX_COMPILATION_CACHE_DIR`` when it is set and
``<repo>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ROUNDS = 3
M = 6                  # participation 0.5 of K=12
ACC_TOL = 1e-2         # batched vs sequential, docs/engine.md §3
BIG_K, BIG_M = 1_000_000, 256
# Pod mesh vs device 0 after one full-width round: per-client math is the
# same, but the grouped-conv lowering and the cross-device reduction order
# differ at the ulp level, and four SGD steps amplify that. Both sides run
# at f32 matmul precision: the TPU's default one-pass bf16 rounding alone
# moves the params of the two lowerings apart by ~1e-4 after one round.
POD_RTOL, POD_ATOL = 1e-3, 1e-4


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}
    log(f"(a) device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    check(d0.platform == "tpu",
          f"JAX found platform {d0.platform!r}, not 'tpu': no accelerator")
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)}")
    return info


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def launch(*extra: str):
    from repro.launch import train

    argv = ["--arch", "resnet18-cifar10", *extra]
    log(f"launch: python -m repro.launch.train {' '.join(argv)}")
    t0 = time.perf_counter()
    res = train.main(argv)
    log(f"launch done in {time.perf_counter() - t0:.1f} s "
        f"(compilation included)")
    return res


def phase_training():
    import numpy as np

    res = launch("--rounds", str(ROUNDS))
    log(f"(b) train_loss={res.train_loss.tolist()} "
        f"{res.metric_name}={res.accuracy.tolist()} "
        f"selection_counts={res.selection_counts.tolist()}")
    check(len(res.train_loss) == ROUNDS and len(res.accuracy) == ROUNDS,
          f"expected {ROUNDS} rounds of metrics")
    check(bool(np.all(np.isfinite(res.train_loss))), "non-finite train loss")
    check(bool(np.all(np.isfinite(res.accuracy))), "non-finite eval metric")
    check(bool(np.all((res.accuracy >= 0) & (res.accuracy <= 1))),
          "eval metric outside [0, 1]")
    check(int(res.selection_counts.sum()) == M * ROUNDS,
          f"selection counts sum to {int(res.selection_counts.sum())}, "
          f"not M × rounds = {M * ROUNDS}")
    return res


def phase_executor(batched):
    import numpy as np

    res = launch("--rounds", "2", "--client-execution", "sequential")
    hist_b = batched.selected_history[:2]
    diff = np.abs(res.accuracy - batched.accuracy[:2])
    log(f"(c) sequential {res.metric_name}={res.accuracy.tolist()} vs "
        f"batched {batched.accuracy[:2].tolist()} (|diff| max "
        f"{float(diff.max()):.6f}); sequential train_loss="
        f"{res.train_loss.tolist()} vs batched "
        f"{batched.train_loss[:2].tolist()}")
    check(np.array_equal(res.selected_history, hist_b),
          f"sequential cohorts {np.flatnonzero(res.selected_history[-1])} "
          f"differ from batched {np.flatnonzero(hist_b[-1])}")
    check(bool(np.all(diff <= ACC_TOL)),
          f"batched vs sequential accuracy differs by {float(diff.max())} "
          f"> {ACC_TOL}")


def cohort_margin(probs_ref, key, m: int) -> str:
    """Gap between the m-th and (m+1)-th perturbed log-probability of the
    jnp sampler — the size of the tie a cohort mismatch sits on."""
    import jax
    import jax.numpy as jnp

    g = jax.random.gumbel(key, probs_ref.shape, probs_ref.dtype)
    vals, _ = jax.lax.top_k(jnp.log(probs_ref + 1e-30) + g, m + 1)
    return f"margin between rank {m} and {m + 1}: {float(vals[m - 1] - vals[m]):.3e}"


def phase_kernel(batched):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core.scoring import HeteRoScoreConfig
    from repro.core.selection import (SelectorConfig, dynamic_temperature,
                                      heterosel_select, make_selector)
    from repro.core.state import init_client_state
    from repro.kernels import ops as kernel_ops

    res = launch("--rounds", "2", "--selector", "heterosel_pallas")
    log(f"(d) heterosel_pallas cohorts "
        f"{[np.flatnonzero(h).tolist() for h in res.selected_history]} vs "
        f"heterosel {[np.flatnonzero(h).tolist() for h in batched.selected_history[:2]]}")
    check(np.array_equal(res.selected_history, batched.selected_history[:2]),
          "heterosel_pallas cohorts differ from heterosel at K=12")
    check(bool(np.all(np.isfinite(res.train_loss))), "non-finite train loss")

    # The selection step the engine jits for this selector (K=12, m=6).
    key = jax.random.PRNGKey(0)
    sel_cfg = SelectorConfig(num_selected=M)
    state12 = init_client_state(12, jnp.linspace(0.0, 0.6, 12))
    step = jax.jit(make_selector("heterosel_pallas", sel_cfg))
    hlo = step.lower(key, state12, jnp.int32(0)).compile().as_text()
    n_custom = hlo.count("tpu_custom_call")
    log(f"(d) compiled heterosel_pallas selection step: "
        f"{n_custom} tpu_custom_call site(s) in its HLO")
    check(n_custom > 0, "no tpu_custom_call in the compiled selection step")
    mask_f, _ = step(key, state12, jnp.int32(0))
    mask_j, _ = jax.jit(make_selector("heterosel", sel_cfg))(
        key, state12, jnp.int32(0))
    check(np.array_equal(np.asarray(mask_f), np.asarray(mask_j)),
          "K=12 fused cohort differs from jnp cohort")

    # Large K: bf16 state on device, seeded.
    k, m = BIG_K, BIG_M
    state = big_state(k, seed=0)
    t = jnp.int32(3)
    big_cfg = SelectorConfig(num_selected=m)
    tau = dynamic_temperature(t, big_cfg)
    score_cfg = HeteRoScoreConfig()
    fused = jax.jit(lambda s, key: kernel_ops.heterosel_topm(
        s, jnp.asarray(t, jnp.float32), tau, m, key, score_cfg,
        interpret=kernel_ops.kernel_interpret()))
    ref = jax.jit(lambda s, key: heterosel_select(
        key, s, t, sel_cfg=big_cfg, score_cfg=score_cfg))
    bkey = jax.random.PRNGKey(7)
    sel_f, p_f, _ = jax.block_until_ready(fused(state, bkey))
    mask_r, p_r = jax.block_until_ready(ref(state, bkey))
    p_f, p_r = np.asarray(p_f), np.asarray(p_r)
    rel = float(np.max(np.abs(p_f - p_r) / np.abs(p_r)))
    coh_f = np.sort(np.asarray(sel_f))
    coh_r = np.flatnonzero(np.asarray(mask_r))
    log(f"(d) K={k} m={m} bf16 state: probs max rel diff {rel:.3e}, "
        f"sum {float(p_f.sum()):.6f}; cohorts identical: "
        f"{np.array_equal(coh_f, coh_r)}")
    check(np.allclose(p_f, p_r, rtol=1e-5, atol=0.0),
          f"K={k} fused probabilities differ from jnp: max rel {rel:.3e}")
    if not np.array_equal(coh_f, coh_r):
        raise SmokeFailure(
            f"K={k} fused cohort differs from jnp in "
            f"{len(np.setxor1d(coh_f, coh_r))} clients; "
            + cohort_margin(jnp.asarray(p_r), bkey, m))


def big_state(k: int, seed: int):
    """Seeded mid-run K-client state (three observed rounds), bf16 rows,
    built on the device."""
    import jax
    import jax.numpy as jnp

    from repro.core.state import init_client_state, to_bf16, update_client_state

    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 10)
    s = init_client_state(k, jax.random.uniform(ks[0], (k,), maxval=0.69))
    for r in range(3):
        s = update_client_state(
            s, round_idx=jnp.int32(r),
            selected_mask=jax.random.uniform(ks[1 + 3 * r], (k,)) < 0.6,
            observed_loss=jax.random.uniform(ks[2 + 3 * r], (k,),
                                             minval=0.1, maxval=4.0),
            observed_sqnorm=jax.random.uniform(ks[3 + 3 * r], (k,),
                                               maxval=2.0))
    return to_bf16(s)


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------


def shard_devices(x) -> list:
    return sorted(s.device.id for s in x.addressable_shards)


def phase_pod_round():
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from repro.configs.base import FedConfig
    from repro.configs.registry import get_config
    from repro.data import make_vision_data
    from repro.fed import FederatedSpec
    from repro.fed import batched as fed_batched
    from repro.models import build_model
    from repro.sharding.rules import POD_AXES

    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), ("pod",))
    fed = FedConfig(num_clients=16, participation=0.5, rounds=1,
                    local_epochs=2, local_batch=16, mu=0.1, seed=0)
    data = make_vision_data(fed, train_per_class=48, test_per_class=16,
                            noise=0.3)
    model = build_model(get_config("resnet18-cifar10"))
    m = fed.num_selected
    log(f"pod round: resnet18-cifar10 d_model={model.cfg.d_model}, K=16, "
        f"M={m}, mesh {dict(mesh.shape)} over devices "
        f"{[d.id for d in devs]}")

    def run(mesh_arg):
        spec = FederatedSpec(model, fed, data, steps_per_round=4,
                             mesh=mesh_arg,
                             mesh_axes=POD_AXES if mesh_arg is not None else None)
        t0 = time.perf_counter()
        with jax.default_device(devs[0]), \
                jax.default_matmul_precision("highest"):
            res = spec.build().run()
        log(f"  {'pod mesh' if mesh_arg is not None else 'device 0'} round "
            f"in {time.perf_counter() - t0:.1f} s: cohort "
            f"{np.flatnonzero(res.selected_history[0]).tolist()}, "
            f"train_loss {res.train_loss.tolist()}")
        return res

    one = run(None)
    pod = run(mesh)

    # Where the executor's shard_cohort puts this cohort, and where the
    # round's outputs live.
    selected = np.flatnonzero(pod.selected_history[0])
    stacked = fed_batched.gather_stacked_batches(
        data, selected, 4, fed.local_batch, np.random.default_rng(0))
    placed = fed_batched.shard_cohort(stacked, mesh, POD_AXES)
    for name, leaf in placed.items():
        log(f"  input {name} {leaf.shape}: shards on devices "
            f"{shard_devices(leaf)}")
    for name, tree in (("device-0 params", one.params),
                       ("pod params", pod.params)):
        leaf = jax.tree_util.tree_leaves(tree)[0]
        log(f"  {name} (first leaf {leaf.shape}) on devices "
            f"{shard_devices(leaf)}")
    check(len(set(shard_devices(placed["images"]))) == 4,
          "cohort input is not spread over 4 devices")

    check(np.array_equal(pod.selected_history, one.selected_history),
          "pod-mesh cohort differs from device-0 cohort")
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(pod.params),
                    jax.tree_util.tree_leaves(one.params)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        worst = max(worst, float(np.max(np.abs(a - b))))
        check(np.allclose(a, b, rtol=POD_RTOL, atol=POD_ATOL),
              f"pod-mesh params differ from device 0: max abs "
              f"{float(np.max(np.abs(a - b))):.3e}")
    log(f"  params allclose (rtol {POD_RTOL}, atol {POD_ATOL}); max abs "
        f"diff {worst:.3e}")


def phase_sharded_select():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.scoring import HeteRoScoreConfig
    from repro.core.selection import SelectorConfig, dynamic_temperature
    from repro.kernels import ops as kernel_ops

    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), ("clients",))
    k, m = BIG_K, BIG_M
    with jax.default_device(devs[0]):
        state = big_state(k, seed=0)
    t = jnp.int32(3)
    tau = dynamic_temperature(t, SelectorConfig(num_selected=m))
    cfg = HeteRoScoreConfig()
    key = jax.random.PRNGKey(7)

    one = jax.jit(lambda s: kernel_ops.heterosel_topm(
        s, jnp.asarray(t, jnp.float32), tau, m, key, cfg,
        interpret=kernel_ops.kernel_interpret()))
    with jax.default_device(devs[0]):
        sel_1, p_1, _ = jax.block_until_ready(one(state))

    placed = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("clients"))), state)
    for name in ("loss_prev", "last_selected"):
        leaf = getattr(placed, name)
        log(f"sharded select input {name} {leaf.shape} {leaf.dtype}: shards "
            f"on devices {shard_devices(leaf)}")
    check(len(set(shard_devices(placed.loss_prev))) == 4,
          "client state is not spread over 4 devices")
    sharded = jax.jit(lambda s: kernel_ops.heterosel_topm_sharded(
        s, jnp.asarray(t, jnp.float32), tau, m, key, cfg, mesh=mesh,
        axis="clients", interpret=kernel_ops.kernel_interpret()))
    hlo = sharded.lower(placed).compile().as_text()
    sel_4, p_4, _ = jax.block_until_ready(sharded(placed))
    log(f"sharded select output probs on devices {shard_devices(p_4)}; "
        f"tpu_custom_call in HLO: {'tpu_custom_call' in hlo}")
    check("tpu_custom_call" in hlo, "no tpu_custom_call in sharded select")
    p_1, p_4 = np.asarray(p_1), np.asarray(p_4)
    rel = float(np.max(np.abs(p_4 - p_1) / np.abs(p_1)))
    same = np.array_equal(np.sort(np.asarray(sel_4)), np.sort(np.asarray(sel_1)))
    log(f"sharded vs device 0 at K={k}, m={m}: probs max rel diff "
        f"{rel:.3e}; cohort identical: {same}")
    check(np.allclose(p_4, p_1, rtol=1e-5, atol=0.0),
          f"sharded probabilities differ: max rel {rel:.3e}")
    check(same, "sharded cohort differs from the device-0 cohort")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"[chip_smoke] FAIL: the repro package is not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    try:
        info = device_info(args.chips)
        from repro.launch.cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        if args.chips == 4:
            phase_pod_round()
            phase_sharded_select()
        else:
            batched = phase_training()
            phase_executor(batched)
            phase_kernel(batched)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
