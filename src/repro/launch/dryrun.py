import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

The two lines above MUST stay first (before any jax import): jax locks the
device count at first init, and the production meshes need 512 placeholder
host devices. Smoke tests / benches import this module never — they see 1.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes

Each record (memory_analysis, cost_analysis, collective bytes by kind,
roofline terms) is appended incrementally to
``benchmarks/results/dryrun_<mesh>.json`` so long sweeps are resumable.
"""

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs.registry import ARCHS, ASSIGNED, get_config, get_shape
from repro.configs.shapes import SHAPES
from repro.launch.mesh import make_production_mesh, mesh_chip_count
from repro.launch.steps import build_plan, depth_variant, outer_trips
from repro.models.layers import set_probe_mode
from repro.roofline import hlo as roofline
from repro.sharding.rules import needs_fsdp

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmarks", "results")


SUFFIX = ""


def _results_path(multi_pod: bool) -> str:
    name = ("dryrun_multipod" if multi_pod else "dryrun_singlepod") + SUFFIX + ".json"
    return os.path.abspath(os.path.join(RESULTS_DIR, name))


def load_results(multi_pod: bool) -> Dict:
    path = _results_path(multi_pod)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(results: Dict, multi_pod: bool) -> None:
    path = _results_path(multi_pod)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _compile_plan(plan, mesh):
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            plan.fn,
            in_shardings=plan.in_shardings,
            out_shardings=plan.out_shardings,
            donate_argnums=plan.donate_argnums,
        )
        lowered = jitted.lower(*plan.args)
        return lowered.compile()


def run_one(arch: str, shape_name: str, mesh, *, multi_pod: bool,
            verbose: bool = True) -> Optional[Dict]:
    """Full-depth compile (memory proof) + depth-1/2 fully-unrolled probes.

    cost_analysis counts scan bodies once, so per-step totals are recovered
    from the probes: with every scan unrolled, f(d) = out + d·body exactly
    ⇒ body = f(2) − f(1), total = f(1) − body + trips·body.
    """
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    fsdp = needs_fsdp(cfg, 16)
    plan = build_plan(cfg, shape, mesh, multi_pod=multi_pod, fsdp=fsdp)
    if plan is None:
        return {"status": "skipped", "reason": "encoder-only: no decode step"}

    t0 = time.time()
    compiled = _compile_plan(plan, mesh)
    t_full = time.time() - t0
    mem = compiled.memory_analysis()
    flops_scan, bytes_scan = roofline.extract_cost(compiled)

    # FLOPs/bytes/collectives probes at depths 1 and 2, fully unrolled.
    probes = {}
    t0 = time.time()
    set_probe_mode(True)
    try:
        for d in (1, 2):
            pcfg = depth_variant(cfg, d)
            pplan = build_plan(pcfg, shape, mesh, multi_pod=multi_pod, fsdp=fsdp)
            pc = _compile_plan(pplan, mesh)
            f, b = roofline.extract_cost(pc)
            probes[d] = {"flops": f, "bytes": b,
                         "coll": roofline.collective_bytes(pc.as_text())}
    finally:
        set_probe_mode(False)
    t_probe = time.time() - t0

    trips = outer_trips(get_config(arch) if not plan.note else cfg)
    f1, f2 = probes[1]["flops"], probes[2]["flops"]
    b1, b2 = probes[1]["bytes"], probes[2]["bytes"]
    flops = max(f1 + (trips - 1) * (f2 - f1), 0.0)
    byts = max(b1 + (trips - 1) * (b2 - b1), 0.0)
    coll = {}
    for kind in roofline.COLLECTIVES:
        c1 = probes[1]["coll"].get(kind, 0)
        c2 = probes[2]["coll"].get(kind, 0)
        coll[kind] = int(max(c1 + (trips - 1) * (c2 - c1), 0))

    chips = mesh_chip_count(mesh)
    # probe modules are per-device programs — scale to fleet totals
    terms = roofline.RooflineTerms(
        flops=flops * chips, hbm_bytes=byts * chips,
        coll_bytes=float(sum(coll.values())) * chips,
        chips=chips,
        model_flops=roofline.model_flops(cfg, shape, shape.kind),
    )
    rec = {
        "status": "ok",
        "note": plan.note,
        "chips": chips,
        "compile_full_s": round(t_full, 2),
        "compile_probe_s": round(t_probe, 2),
        "flops_scan_counted_once": flops_scan,
        "bytes_scan_counted_once": bytes_scan,
        "outer_trips": trips,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_chip_total_bytes": (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            ) // chips,
        },
        "collectives": coll,
        "roofline": terms.as_dict(),
    }
    if verbose:
        r = rec["roofline"]
        print(
            f"  {arch:24s} {shape_name:12s} "
            f"comp={r['t_compute_s']*1e3:9.3f}ms mem={r['t_memory_s']*1e3:9.3f}ms "
            f"coll={r['t_collective_s']*1e3:9.3f}ms -> {r['bottleneck']:10s} "
            f"useful={r['useful_flops_ratio']:.2f} "
            f"(full {t_full:.0f}s probe {t_probe:.0f}s) {plan.note}"
        )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one architecture id")
    ap.add_argument("--shape", default=None, help="one input-shape name")
    ap.add_argument("--all", action="store_true", help="all assigned arch × shapes")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute existing records")
    ap.add_argument("--suffix", default="", help="results-file suffix (e.g. _opt)")
    args = ap.parse_args()
    global SUFFIX
    SUFFIX = args.suffix

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(SHAPES)

    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        results = load_results(mp)
        print(f"== mesh {'2x16x16 multi-pod' if mp else '16x16 single-pod'} "
              f"({mesh_chip_count(mesh)} chips) ==")
        for arch in archs:
            for shape_name in shapes:
                key = f"{arch}|{shape_name}"
                if not args.force and key in results and results[key].get("status") == "ok":
                    continue
                try:
                    rec = run_one(arch, shape_name, mesh, multi_pod=mp)
                except Exception as e:  # record failures — they are bugs to fix
                    rec = {"status": "error", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"  {arch:24s} {shape_name:12s} ERROR {type(e).__name__}: {str(e)[:160]}")
                results[key] = rec
                save_results(results, mp)
        ok = sum(1 for r in results.values() if r.get("status") == "ok")
        sk = sum(1 for r in results.values() if r.get("status") == "skipped")
        er = sum(1 for r in results.values() if r.get("status") == "error")
        print(f"== done: {ok} ok, {sk} skipped, {er} errors ==")


if __name__ == "__main__":
    main()
