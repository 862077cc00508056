"""Production serving launcher, built on the ``repro.serve`` subsystem.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --requests 8 [--ckpt-dir /path]

Drives a seeded request stream through a continuous-batching
``ModelServer`` (per-request KV-cache slots, versioned params registry) and
prints the latency/throughput summary. ``--oneshot`` instead runs one
batched ``serve.generate`` call — the vlm families always take that path,
since their decode needs a pre-warmed cross-attention cache the slot
lifecycle does not manage.

``--ckpt-dir`` resolution is loud and layered:

  * PR-7 federated round snapshots (``fedround_*.npz``) are authoritative —
    the newest round is restored params-only (``restore_federated_round``
    with ``subset=True``) and the launcher prints which round and engine
    kind it is serving. A template/snapshot disagreement raises
    ``CheckpointMismatchError``; there is NO silent fallback to the legacy
    format when fedround files exist.
  * Otherwise the legacy flat ``ckpt_*.npz`` format is tried, and says so.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from repro.ckpt import (list_federated_rounds, read_federated_meta,
                        restore_checkpoint, restore_federated_round)
from repro.configs.registry import get_config, smoke_variant
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.models import vlm as vlm_mod
from repro.serve import (ModelServer, TrafficConfig, TrafficSimulator,
                         VersionRegistry, generate)


def resolve_params(model, ckpt_dir):
    """(params, description, round) for ``--ckpt-dir``; see module docstring.

    ``round`` is the federated round the params came from, or -1 for a
    fresh init / legacy checkpoint.
    """
    params = model.init_params(jax.random.PRNGKey(0))
    if not ckpt_dir:
        return params, "fresh init (no --ckpt-dir)", -1
    if list_federated_rounds(ckpt_dir):
        meta = read_federated_meta(ckpt_dir)
        # Params-only subset restore out of the full engine snapshot;
        # keypath/dtype mismatches raise CheckpointMismatchError — loudly,
        # with no legacy fallback.
        trees, _, _ = restore_federated_round(
            ckpt_dir, likes={"params": params}, round_idx=meta["round"],
            subset=True)
        desc = (f"federated round {meta['round']} snapshot "
                f"({meta['engine']} engine)")
        return trees["params"], desc, int(meta["round"])
    params, meta = restore_checkpoint(ckpt_dir, params)
    return params, f"legacy checkpoint {meta}", -1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve from a PR-7 federated round snapshot "
                         "(fedround_*.npz, params-only restore) or a legacy "
                         "ckpt_*.npz directory")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of simulated requests to serve")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate (requests per virtual unit)")
    ap.add_argument("--batch", type=int, default=4,
                    help="server slot width (continuous-batching lanes); "
                         "oneshot: the decode batch size")
    ap.add_argument("--tokens", type=int, default=16,
                    help="max output tokens per request (oneshot: exactly)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--oneshot", action="store_true",
                    help="one batched generate() call instead of the "
                         "traffic-driven server (vlm always does this)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    enable_compile_cache()
    model = build_model(cfg)
    if not model.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only — no decode serving")

    params, desc, round_idx = resolve_params(model, args.ckpt_dir)
    print(f"serving {cfg.name} from {desc}")

    prompt_len = (2, 6)
    if args.oneshot or cfg.family == "vlm":
        b, plen = args.batch, 4
        cache = model.init_cache(b, plen + args.tokens)
        if cfg.family == "vlm":
            ve = jax.random.normal(jax.random.PRNGKey(1),
                                   (b, cfg.vision_tokens, cfg.d_model))
            cache = vlm_mod.warm_cross_cache(cfg, params, cache, ve)
            print("vlm: cross-attention cache warmed (oneshot path)")
        key = jax.random.PRNGKey(args.seed + 2)
        prompt = jax.random.randint(key, (b, plen), 0, cfg.vocab_size)
        t0 = time.time()
        gen = generate(model, params, prompt, args.tokens, key, cache=cache)
        dt = time.time() - t0
        print(f"arch={cfg.name} batch={b}: {args.tokens} tokens in {dt:.2f}s "
              f"({b * args.tokens / dt:.1f} tok/s)")
        print(gen)
        return

    registry = VersionRegistry()
    registry.publish(params, round_idx=round_idx)
    max_len = prompt_len[1] + args.tokens
    server = ModelServer(model, registry, batch_width=args.batch,
                         max_len=max_len)
    sim = TrafficSimulator(
        TrafficConfig(rate=args.rate, prompt_len=prompt_len,
                      output_len=(max(1, args.tokens // 2), args.tokens),
                      seed=args.seed, max_requests=args.requests),
        vocab=cfg.vocab_size)
    for req in sim.take(args.requests):
        server.submit(req, arrival_round=round_idx)
    records = server.pump()
    s = server.metrics.summary()
    print(f"arch={cfg.name} slots={args.batch}: served {s['requests']} "
          f"requests / {s['total_tokens']} tokens at "
          f"{s['decode_tok_per_s']:.1f} tok/s "
          f"(mean latency {s['total_ms_mean']:.1f} ms, "
          f"p95 {s['total_ms_p95']:.1f} ms)")
    v = registry.latest()
    print(f"served by version {v.version} (round {v.round})")
    for rec in records[:4]:
        print(f"  rid {rec.rid}: {rec.prompt_len}+{rec.output_len} tokens "
              f"-> {list(rec.tokens)[:8]}")


if __name__ == "__main__":
    main()
