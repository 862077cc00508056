"""Readings that set a cell's limits, on the chip, in one process.

    python benchmarks/chip/calibrate.py --workload <cell> --seeds 12 --controls 3

For each seed the program runs the cell's compared rounds through its own
entry (no window) and is compared with the reference, as every benchmark
run does: the sound readings, whose largest is a limit's lower end. For the
first ``--controls`` seeds the same comparison is also made of:

  control          the reference computed one precision below the
                   configuration, weights stored there too (``weights``),
                   put in the program's place;
  control_compute  the same with the configured weights kept and only the
                   network (forward, norms, gradients, eval) computed one
                   precision below: mixed precision;
  half_batch       the reference with each step's batch halved, the mean
                   taken over the rest;
  unchanged        the program's first state handed back unchanged by every
                   round (reads 1 by construction);
  cohort_off       the program's cohort shifted by one client id, as a mask
                   altered where it is produced;
  eval_low         the eval computed one precision below, on the
                   reference's params;
  half_eval        the eval over the first half of the eval batch.

One JSON line per reading, then a summary: the largest sound reading and
the smallest of the control and of each fault, per number.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_001)
    args = ap.parse_args()

    import numpy as np
    from compare import NUMBERS, readings, reference_run

    cell = run.load_cell(args.workload)
    cell["name"] = args.workload
    federation = importlib.import_module(f"reference.{cell['reference']}")
    device = run.check_device(cell["chips"])
    run.enable_cache()
    counter = run.CompileCounter()
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        data, hook, fed_seed = run.drive(cell, seed, None, None, counter)
        snap, loss, metric, cohorts = hook.snap, hook.loss, hook.metric, hook.cohorts
        prog = dict(snap, loss=loss, metric=metric)
        controls = i < args.controls
        ref = reference_run(cell, data, fed_seed, cohorts, eval_variants=controls)
        got = {"seed": seed, "kind": "program", **readings(prog, ref)}
        rows.append(got)
        print(json.dumps(got), flush=True)
        if controls:
            for kind, opts in (("control", {"control": "weights"}),
                               ("control_compute", {"control": "compute"}),
                               ("half_batch", {"half_batch": True})):
                other = reference_run(cell, data, fed_seed, cohorts, **opts)
                rows.append({"seed": seed, "kind": kind, **readings(other, ref)})
            same = dict(prog, p1=snap["p0"], p_last=snap["p0"])
            rows.append({"seed": seed, "kind": "unchanged", **readings(same, ref)})
            m, k = cell["fed"]["num_selected"], data.num_clients
            off = [federation.cohort_gap(q, (np.asarray(c) + 1) % k, m)
                   for q, c in zip(ref["q"], cohorts)]
            rows.append({"seed": seed, "kind": "cohort_off", **readings(prog, ref),
                         "cohort_gap": float(max(off))})
            for kind, key in (("eval_low", "metric_low"), ("half_eval", "metric_half")):
                rows.append({"seed": seed, "kind": kind,
                             **readings(dict(prog, metric=ref[key]), ref)})
            for r in rows[-7:]:
                print(json.dumps(r), flush=True)
        print(f"# seed {seed}: {time.perf_counter() - t0:.1f} s", flush=True)
    summary = {"workload": args.workload, "device": device}
    for kind in ("program", "control", "control_compute", "half_batch", "unchanged",
                 "cohort_off", "eval_low", "half_eval"):
        sel = [r for r in rows if r["kind"] == kind]
        if sel:
            agg = max if kind == "program" else min
            summary[kind] = {n: agg(r[n] for r in sel) for n in NUMBERS}
            summary[kind]["seeds"] = len(sel)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
