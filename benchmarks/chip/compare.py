"""The comparison that decides ``correct``.

The set-up rounds of a run go through the window's own entry; the check
replays them in the plain reference (the workload's federation reference,
with the configuration's reference model) from the same seed and the same logged batches, and
compares these numbers, each against the cell's limit:

  loss_gap    each round's mean cohort loss, as the largest relative gap;
  grad_gap    the first round's global update w1 - w0 (the pseudo-gradient
              the server applies), leaf by leaf;
  change_gap  the parameters' change after the compared rounds, w3 - w0,
              leaf by leaf;
  cohort_gap  each round's cohort against the reference's own Gumbel-top-m
              selection from its own metadata (0 when equal; a near-tie at
              the cut reads near 0, a wrong cohort O(1));
  eval_gap    each round's eval metric as the engine reported it (accuracy
              over the whole eval batch, or exp(-loss) for a language
              model) against the reference's over the same batch, as the
              largest relative gap.

A leaf's gap is | ||d_program|| - ||d_reference|| | over the larger of its
reference norm and the median leaf's; the worst leaf counts. A cell
compares the numbers its workload file gives limits for. Leaves whose
reference first update is under a thousandth of the median leaf's move by
round-off alone and are left out. Stacked per-layer leaves of the program
are split into their layers, so each layer's leaf counts alone.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "cohort_gap", "eval_gap")
ROUNDS = 3
NOUGHT = 1e-3  # a leaf moved by round-off alone: under this share of the median


def flatten(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    """path -> float64 array; a ``layers`` list or a dict of stacked layer
    leaves both come out as ``layers/<i>/...``."""
    out: Dict[str, np.ndarray] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            path = f"{prefix}{k}"
            if k == "layers" and isinstance(v, dict):
                for sub, arr in flatten(v).items():
                    for i in range(arr.shape[0]):
                        out[f"{path}/{i}/{sub}"] = arr[i]
            else:
                out.update(flatten(v, path + "/"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(flatten(v, f"{prefix}{i}/"))
    else:
        out[prefix.rstrip("/")] = np.asarray(tree).astype(np.float64)
    return out


def _delta_norms(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm((a[k] - b[k]).ravel())) for k in b}


def leaf_gaps(cand: Dict[str, float], ref: Dict[str, float], keep: List[str]) -> np.ndarray:
    med = float(np.median([ref[k] for k in keep]))
    return np.asarray([abs(cand[k] - ref[k]) / max(ref[k], med) for k in keep])


def readings(cand: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers of a candidate run (the program's, the control's or a
    planted fault's) against the reference's."""
    r0, r1, r3 = (flatten(ref[k]) for k in ("p0", "p1", "p_last"))
    c0, c1, c3 = (flatten(cand[k]) for k in ("p0", "p1", "p_last"))
    if set(r0) != set(c0) or any(r0[k].shape != c0[k].shape for k in r0):
        raise ValueError("program and reference parameter trees differ")
    g_ref, g_cand = _delta_norms(r1, r0), _delta_norms(c1, c0)
    med = float(np.median(list(g_ref.values())))
    keep = [k for k, v in g_ref.items() if v >= NOUGHT * med]
    d_ref, d_cand = _delta_norms(r3, r0), _delta_norms(c3, c0)
    loss = max(abs(a - b) / abs(b) for a, b in zip(cand["loss"], ref["loss"]))
    grad, change = leaf_gaps(g_cand, g_ref, keep), leaf_gaps(d_cand, d_ref, keep)
    evals = max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(cand["metric"], ref["metric"]))
    return {"loss_gap": float(loss),
            "grad_gap": float(grad.max()), "change_gap": float(change.max()),
            "cohort_gap": float(max(ref["cohort_gap"])), "eval_gap": float(evals)}


def reference_run(cell: Dict[str, Any], data, fed_seed: int, cohorts: List[np.ndarray],
                  **options) -> Dict[str, Any]:
    """The cell's federation reference (its workload's ``reference``) over
    the compared rounds, with the configuration's reference model;
    ``options`` go to its ``run`` (a control, a planted fault)."""
    cfg = cell["config_file"]
    federation = importlib.import_module(f"reference.{cell['reference']}")
    model = importlib.import_module(f"reference.{cfg['reference']}")
    out = federation.run(model=model, cfg=cfg, data=data, log=data.log, fed=cell["fed"],
                         spec=cell["spec"], seed=fed_seed, rounds=ROUNDS, cohorts=cohorts,
                         **options)
    import jax
    for k in ("p0", "p1", "p_last"):
        out[k] = jax.tree_util.tree_map(np.asarray, jax.device_get(out[k]))
    return out


def compare_run(cell: Dict[str, Any], data, snap: Dict[str, Any], loss: List[float],
                metric: List[float], cohorts: List[np.ndarray],
                fed_seed: int) -> Dict[str, Dict[str, float]]:
    """Each number the cell compares (the keys of its ``limits``) beside its limit."""
    ref = reference_run(cell, data, fed_seed, cohorts)
    got = readings(dict(snap, loss=loss, metric=metric), ref)
    return {n: {"value": got[n], "limit": float(lim)} for n, lim in cell["limits"].items()}
