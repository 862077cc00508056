"""Production federated-training launcher.

Trains ``--arch`` at its full published width on whatever devices JAX
sees (one TPU v5e chip holds the paper's ResNet-18 cohort); only ``--smoke``
swaps in the reduced variant. Defaults are the paper's federation: K=12
clients, participation 0.5, Dirichlet α=0.1, FedProx μ=0.1, client
lr 0.01, HeteRo-Select, batched client execution.

    PYTHONPATH=src python -m repro.launch.train --arch resnet18-cifar10 \
        --rounds 5 [--smoke] [--ckpt-dir /tmp/ckpt]

``main(argv)`` returns the run's ``FLResult``, so a script can drive the
launcher itself (``chip_smoke.py`` does).

The control plane (HeteRo-Select scoring over client metadata) always runs
on the host exactly as in the paper; the data plane (FedProx local steps)
is jitted and, when a multi-device mesh exists, sharded via sharding/rules.

``--ckpt-dir`` enables mid-run checkpoint/resume via the engine's
``CheckpointHook``: every ``--ckpt-every`` rounds the full resumable state
(params, client metadata, RNG streams, plus each engine's extras — the
async virtual clock and in-flight buffers, the hierarchical edge state) is
written, and a relaunch with the same directory resumes where the killed
run stopped — under every ``--round-policy`` / ``--topology`` combination.
``--ckpt-keep N`` garbage-collects all but the newest N snapshots.

``--telemetry`` / ``--trace-dir DIR`` attach the telemetry plane
(``repro.obs``): dual-timeline tracing of every round phase, per-round
fairness/score/communication telemetry, and the online stability report.
With ``--trace-dir`` the run writes ``trace.jsonl``, a Perfetto-loadable
``trace.json``, and ``report.md``/``report.json`` under DIR; telemetry is
bitwise non-perturbing to training and resume-safe alongside
``--ckpt-dir``.
"""

from __future__ import annotations

import argparse
import math
from typing import Optional, Sequence

import jax
import numpy as np

from repro.configs.base import FedConfig
from repro.configs.registry import get_config, smoke_variant
from repro.data import make_lm_data, make_vision_data
from repro.fed import (AsyncConfig, CheckpointHook, FederatedSpec,
                       FLResult, HierarchyConfig)
from repro.fed.availability import SystemProfile
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.ckpt import save_checkpoint


def main(argv: Optional[Sequence[str]] = None) -> FLResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet18-cifar10")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--participation", type=float, default=0.5)
    ap.add_argument("--mu", type=float, default=0.1)
    ap.add_argument("--lr", type=float, default=FedConfig.lr,
                    help="client SGD step size (default: the paper's; the "
                         "full-width ResNet-18 diverges at 0.3)")
    ap.add_argument("--selector", default="heterosel")
    ap.add_argument("--aggregator", default="fedavg",
                    choices=["fedavg", "fedavg_weighted", "fedavgm"])
    ap.add_argument("--client-execution", default="batched",
                    choices=["batched", "sequential"],
                    help="whole cohort in one vmapped call, or one call per "
                         "client (the numerical reference)")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of --arch")
    ap.add_argument("--ckpt-dir", default=None,
                    help="enable mid-run checkpoint/resume under this dir "
                         "(works with every --round-policy / --topology)")
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--ckpt-keep", type=int, default=0,
                    help="keep only the newest N round snapshots "
                         "(0 = keep all)")
    ap.add_argument("--round-policy", default="sync", choices=["sync", "async"],
                    help="sync barrier rounds vs event-driven async rounds")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="async round deadline in virtual-time units "
                         "(0 = no deadline: wait for the full cohort)")
    ap.add_argument("--over-select", type=float, default=0.0,
                    help="async over-selection fraction ε (dispatch m·(1+ε))")
    ap.add_argument("--system-sigma", type=float, default=0.0,
                    help="log-normal sigma of per-client round-time "
                         "multipliers (0 = homogeneous fleet)")
    ap.add_argument("--topology", default="flat",
                    choices=["flat", "hierarchical"],
                    help="flat client→cloud vs two-tier client→edge→cloud "
                         "rounds (fed/hierarchy.py)")
    ap.add_argument("--edges", type=int, default=0,
                    help="hierarchical: number of edge groups E (required)")
    ap.add_argument("--edge-budget", type=int, default=0,
                    help="hierarchical: per-edge inner budget m_e "
                         "(0 = distribute m across edges by size)")
    ap.add_argument("--edges-per-round", type=int, default=0,
                    help="hierarchical: outer cross-edge budget "
                         "(0 = all edges every round)")
    ap.add_argument("--telemetry", action="store_true",
                    help="attach the telemetry plane (repro.obs): per-round "
                         "fairness/score/communication telemetry + online "
                         "stability report, recorded in memory")
    ap.add_argument("--trace-dir", default=None,
                    help="write telemetry artifacts under this dir "
                         "(trace.jsonl stream, Perfetto-loadable trace.json, "
                         "report.md/report.json); implies --telemetry")
    args = ap.parse_args(argv)
    print(f"[train] compile cache: {enable_compile_cache()}")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)

    if args.topology == "hierarchical" and args.edges < 1:
        ap.error("--topology hierarchical requires --edges E (≥ 1)")
    if args.topology != "hierarchical" and (
            args.edges or args.edge_budget or args.edges_per_round):
        ap.error("--edges/--edge-budget/--edges-per-round only take effect "
                 "with --topology hierarchical (flat rounds have no edge "
                 "tier)")
    fed = FedConfig(num_clients=args.clients, participation=args.participation,
                    rounds=args.rounds, local_epochs=2, local_batch=16,
                    lr=args.lr, mu=args.mu, selector=args.selector, seed=0,
                    client_execution=args.client_execution,
                    topology=args.topology, edge_count=args.edges,
                    edge_budget=args.edge_budget)
    if cfg.family == "resnet":
        data = make_vision_data(fed, train_per_class=48, test_per_class=16, noise=0.3)
    else:
        data = make_lm_data(fed, vocab=cfg.vocab_size, seq_len=32)

    model = build_model(cfg)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0))))
    print(f"[train] {cfg.name}: d_model={cfg.d_model}, layers={cfg.num_layers}, "
          f"{n_params / 1e6:.2f} M params; devices: {jax.devices()}")
    hooks = []
    telemetry_hook = None
    if args.telemetry or args.trace_dir:
        from repro.obs import TelemetryHook

        telemetry_hook = TelemetryHook(trace_dir=args.trace_dir)
        # Before CheckpointHook, so hook-state positions stay stable when a
        # telemetry-attached run is resumed (state is keyed by position).
        hooks.append(telemetry_hook)
    if args.ckpt_dir:
        # Checkpoint/resume works under every round_policy × topology
        # combination: each engine persists its extras (virtual clock,
        # in-flight buffers, edge state) via the extra_state protocol.
        hooks.append(CheckpointHook(args.ckpt_dir, every=args.ckpt_every,
                                    resume=True,
                                    keep_last=args.ckpt_keep or None))
    if args.system_sigma > 0 and args.round_policy != "async":
        ap.error("--system-sigma only takes effect with --round-policy async "
                 "(sync rounds have no clock)")
    system = (SystemProfile(args.clients, sigma=args.system_sigma, seed=0)
              if args.system_sigma > 0 else None)
    async_cfg = None
    if args.round_policy == "async":
        async_cfg = AsyncConfig(
            deadline=args.deadline if args.deadline > 0 else math.inf,
            over_select_frac=args.over_select)
    hier_cfg = (HierarchyConfig(edges_per_round=args.edges_per_round)
                if args.topology == "hierarchical" else None)
    spec = FederatedSpec(model, fed, data, steps_per_round=4,
                         aggregator=args.aggregator, hooks=hooks, verbose=True,
                         round_policy=args.round_policy, async_cfg=async_cfg,
                         system=system, hier_cfg=hier_cfg)
    res = spec.build().run()
    print(f"\nfinal metrics ({res.metric_name}):", res.labeled_summary())
    if res.telemetry is not None:
        stab = res.telemetry["stability"]
        print(f"telemetry: stability drop {stab['stability_drop']:.4f} "
              f"(peak {stab['peak']:.4f} → final {stab['final']:.4f}), "
              f"final participation Gini {stab['final_part_gini']:.3f}, "
              f"selection entropy {stab['final_sel_entropy']:.3f}")
        if telemetry_hook is not None and telemetry_hook.report_paths:
            md, js = telemetry_hook.report_paths
            print(f"telemetry artifacts: {md}  {js}")
        if "trace_json" in res.telemetry:
            print(f"trace (load in Perfetto): {res.telemetry['trace_json']}")
    if res.wall_clock is not None and len(res.wall_clock):
        print(f"simulated wall-clock: {res.wall_clock[-1]:.2f} units "
              f"(mean staleness {float(res.round_staleness.mean()):.2f})")
    if res.cloud_uploads is not None:
        # Flat counterfactual: m client uploads every round, regardless of
        # how many edges were active or in flight here.
        print(f"edge→cloud uploads: {int(res.cloud_uploads.sum())} aggregates "
              f"over {fed.rounds} rounds (flat selection would ship "
              f"{fed.num_selected * fed.rounds} client updates)")
    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, res.params, step=fed.rounds,
                               extra=res.summary())
        print("checkpoint:", path)
    return res


if __name__ == "__main__":
    main()
