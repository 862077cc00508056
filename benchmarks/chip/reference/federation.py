"""Plain HeteRo-Select federation (arXiv:2508.06692, Algorithm 1).

One synchronous flat round: score every client (Eqs 1-11, additive form
with the paper's champion weights), softmax with the dynamic temperature
(Eq 12), Gumbel-top-m sampling without replacement; each selected client
runs FedProx SGD, w <- w - lr (grad L(w) + mu (w - w_global)), over its
batches; the server takes the unweighted mean (line 26) and folds each
participant's mean local loss and squared update norm into its metadata
(line 24); the global model is then evaluated on the eval batch, as the
system reports it every round.

Written straight from the paper with numpy-style jax, one client at a time
and at ``highest`` matrix precision. It imports nothing of the system under
test. The model is a reference module (``init``/``loss``) named by the
configuration file; the batches are replayed from the benchmark's own
traffic log.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

NEVER = -(10 ** 6)
EPS = 1e-8
# The paper's champion configuration (Sec III-B).
ETA, GAMMA, ALPHA, T_MAX, DECAY_ROUNDS = 0.3, 0.7, 0.5, 20, 100
TAU0 = 1.0


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------


def init_state(label_js: np.ndarray, compact: bool) -> Dict[str, jax.Array]:
    """Per-client metadata; float fields in bfloat16 when ``compact``."""
    k = len(label_js)
    fdt = jnp.bfloat16 if compact else jnp.float32
    z = jnp.zeros((k,), fdt)
    js = jnp.asarray(np.asarray(label_js, np.float32)).astype(fdt)
    return {"loss_prev": z, "loss_prev2": z, "label_js": js,
            "part_count": jnp.zeros((k,), jnp.int32),
            "last_selected": jnp.full((k,), NEVER, jnp.int32),
            "update_sqnorm": z, "has_loss": z, "has_momentum": z}


def scores(st: Dict[str, jax.Array], t: float) -> jax.Array:
    f = {k: v.astype(jnp.float32) for k, v in st.items()}
    seen = f["has_loss"] > 0
    loss = f["loss_prev"]
    lmin = jnp.min(jnp.where(seen, loss, 1e30))
    lmax = jnp.max(jnp.where(seen, loss, -1e30))
    value = jnp.where(seen, jnp.clip((loss - lmin) / (lmax - lmin + EPS), 0.0, 1.0), 0.5)
    div = f["label_js"] * 2.0 * (1.0 - 0.5 * min(t / DECAY_ROUNDS, 1.0))
    m = jnp.where(f["has_momentum"] > 0, (f["loss_prev2"] - loss) / (f["loss_prev2"] + EPS), 0.0)
    mom = 2.0 / (1.0 + jnp.exp(-5.0 * m)) - 0.5
    h = f["part_count"]
    fair = (1.0 + ETA * h / jnp.maximum(jnp.max(h), 1.0)) ** -2
    stale = jnp.minimum(jnp.maximum(t - f["last_selected"], 0.0), T_MAX)
    st_f = 1.0 + GAMMA * jnp.log1p(stale)
    avg = jnp.sum(jnp.where(seen, f["update_sqnorm"], 0.0)) / jnp.maximum(jnp.sum(seen), 1)
    r = jnp.where(seen, f["update_sqnorm"] / (avg + EPS), 1.0)
    norm = 1.0 - ALPHA * (2.0 / (1.0 + jnp.exp(-3.0 * r)) - 1.0)
    return value + div + mom + (fair - 1.0) + (st_f - 1.0) + (norm - 1.0)


def perturbed_logits(st, t: int, key, k: int) -> jax.Array:
    """log p_k + Gumbel noise: the top m of these are the cohort."""
    tau = TAU0 * (1.0 - 0.5 * min(t / DECAY_ROUNDS, 1.0))
    z = scores(st, float(t)) / tau
    return jax.nn.log_softmax(z) + jax.random.gumbel(key, (k,), jnp.float32)


def cohort_gap(q: np.ndarray, cohort: np.ndarray, m: int) -> float:
    """How far a cohort departs from the top m of ``q``: 0 when it is the top
    m, else the largest logit by which a left-out top-m client beats a chosen
    one (a near-tie at the cut reads near 0, a wrong cohort reads O(1))."""
    top = set(np.argsort(-q, kind="stable")[:m].tolist())
    got = set(int(c) for c in cohort)
    if len(got) != m:
        return float("inf")
    missed, extra = top - got, got - top
    if not missed:
        return 0.0
    return float(max(q[i] for i in missed) - min(q[j] for j in extra))


def update_state(st, t: int, sel: np.ndarray, loss: np.ndarray, sq: np.ndarray):
    """Fold participants' observations into the metadata (Alg. 1 line 24)."""
    k = st["loss_prev"].shape[0]
    mask = np.zeros(k, bool)
    mask[sel] = True
    obs_l = np.zeros(k, np.float32)
    obs_s = np.zeros(k, np.float32)
    obs_l[sel], obs_s[sel] = loss, sq
    mask, obs_l, obs_s = jnp.asarray(mask), jnp.asarray(obs_l), jnp.asarray(obs_s)
    fdt = st["loss_prev"].dtype
    return {
        "loss_prev": jnp.where(mask, obs_l.astype(fdt), st["loss_prev"]),
        "loss_prev2": jnp.where(mask, st["loss_prev"], st["loss_prev2"]),
        "label_js": st["label_js"],
        "part_count": st["part_count"] + mask.astype(jnp.int32),
        "last_selected": jnp.where(mask, t, st["last_selected"]),
        "update_sqnorm": jnp.where(mask, obs_s.astype(fdt), st["update_sqnorm"]),
        "has_loss": jnp.where(mask, jnp.ones((), fdt), st["has_loss"]),
        "has_momentum": jnp.where(mask & (st["has_loss"] > 0), jnp.ones((), fdt),
                                  st["has_momentum"]),
    }


# ---------------------------------------------------------------------------
# Local training and aggregation
# ---------------------------------------------------------------------------


def make_client_visit(loss_fn: Callable, lr: float, mu: float, half_batch: bool = False):
    """Jitted FedProx visit of one client: (w_global, batches) ->
    (w_local, mean loss, ||w_local - w_global||^2). Weights stay in their
    stored dtype between steps; gradients and updates are float32.
    ``half_batch`` plants a fault: each step sees half its batch."""

    def visit(anchor, batches):
        w = anchor
        losses = []
        steps = jax.tree_util.tree_leaves(batches)[0].shape[0]
        for s in range(steps):
            b = jax.tree_util.tree_map(lambda x: x[s], batches)
            if half_batch:
                b = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], b)
            w32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), w)
            a32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), anchor)
            l, g = jax.value_and_grad(loss_fn)(w32, b)
            w = jax.tree_util.tree_map(
                lambda wi, wf, gi, ai: (wf - lr * (gi + mu * (wf - ai))).astype(wi.dtype),
                w, w32, g, a32)
            losses.append(l)
        sq = sum(jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)
                 for x, y in zip(jax.tree_util.tree_leaves(w), jax.tree_util.tree_leaves(anchor)))
        return w, jnp.mean(jnp.stack(losses)), sq

    return jax.jit(visit)


IMPLEMENTED = {"executor": ("batched", "sequential"), "aggregator": ("fedavg",)}


def check_spec(spec: Dict[str, Any]) -> None:
    """Refuse a federation this reference does not implement: a cell whose
    spec asks for another aggregator, compression, topology or round policy
    names a reference of its own."""
    for key, value in spec.items():
        if key in IMPLEMENTED:
            if value not in IMPLEMENTED[key]:
                raise ValueError(f"reference.federation implements {key} in "
                                 f"{IMPLEMENTED[key]}, the cell asks for {value!r}")
        elif key not in ("compact_state", "mesh"):
            raise ValueError(f"reference.federation does not implement {key!r}")


def run(*, model, cfg: Dict[str, Any], data, log: List, fed: Dict[str, Any],
        spec: Dict[str, Any], seed: int, rounds: int, cohorts: List[np.ndarray],
        control: Optional[str] = None, half_batch: bool = False,
        eval_variants: bool = False) -> Dict[str, Any]:
    """``rounds`` reference rounds over the logged batches.

    Returns the params before round 0, after round 0 and after the last
    round, each round's mean cohort loss and eval metric (the model's
    ``metric`` over the whole eval batch, as the system reports it), and
    each round's ``cohort_gap`` of the system's cohort (``cohorts[t]``)
    against this reference's own selection from its own metadata. Training
    follows the logged cohort, the one whose batches exist.

    ``control`` computes the reference one precision below the
    configuration: ``"weights"`` stores and computes the model there,
    ``"compute"`` keeps the configured weights and computes the network
    (forward, norms, gradients, eval) there. ``eval_variants`` adds each
    round's eval computed one precision below (``metric_low``) and over the
    first half of the eval batch (``metric_half``)."""
    check_spec(spec)
    with jax.default_matmul_precision("highest"):
        params = model.init(jax.random.PRNGKey(seed + 1), cfg, low=control == "weights")
        low = control is not None
        loss_fn = functools.partial(model.loss, cfg=cfg, low=low)
        visit = make_client_visit(loss_fn, fed["lr"], fed["mu"], half_batch)
        st = init_state(data.label_js, spec.get("compact_state", False))
        eval_batch = data.eval_batch()
        n_eval = jax.tree_util.tree_leaves(eval_batch)[0].shape[0]
        eval_half = jax.tree_util.tree_map(lambda x: x[: n_eval // 2], eval_batch)
        key = jax.random.PRNGKey(seed)
        k, m = data.num_clients, fed["num_selected"]
        out: Dict[str, Any] = {"p0": params, "loss": [], "metric": [], "cohort_gap": [],
                               "q": [], "metric_low": [], "metric_half": []}
        entries = iter(log)
        for t in range(rounds):
            key, sk = jax.random.split(key)
            q = np.asarray(perturbed_logits(st, t, sk, k))
            out["cohort_gap"].append(cohort_gap(q, cohorts[t], m))
            out["q"].append(q)
            sel: List[int] = []
            total, losses, sqs = None, [], []
            while len(sel) < m:
                ids, batches = data.replay(next(entries))
                for i, kid in enumerate(ids):
                    b = jax.tree_util.tree_map(lambda x: jnp.asarray(x[i]), batches)
                    w, l, sq = visit(params, b)
                    w32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), w)
                    total = w32 if total is None else jax.tree_util.tree_map(jnp.add, total, w32)
                    sel.append(int(kid))
                    losses.append(float(l))
                    sqs.append(float(sq))
            params = jax.tree_util.tree_map(lambda s, p: (s / m).astype(p.dtype), total, params)
            st = update_state(st, t, np.asarray(sel), np.asarray(losses), np.asarray(sqs))
            out["loss"].append(float(np.mean(losses)))
            out["metric"].append(model.metric(params, eval_batch, cfg, low=low))
            if eval_variants:
                out["metric_low"].append(model.metric(params, eval_batch, cfg, low=True))
                out["metric_half"].append(model.metric(params, eval_half, cfg, low=low))
            if t == 0:
                out["p1"] = params
        out["p_last"] = params
        return out
