"""Faults planted in the program's timed path, for the per-cell fault tests.

Each cell is driven end to end on the CPU at a tiny size (the harness's
look for a chip skipped), once as it is and once with each fault its
federation can have planted in the program: a local step that hands its
state back unchanged, half of each batch left out with the mean taken over
the rest, the cohort altered where it is produced (every selected id
shifted by one), and, in a cell that compares the eval, the eval taken over
half its batch. The sound run reads correct; every fault reads not.
"""

import chipbench_tiny
import repro.fed.batched as fed_batched
import repro.fed.engine as fed_engine
from repro.fed.client import LocalResult, local_train

_default_eval = fed_engine.default_eval

FAULTS = ("none", "unchanged", "half_batch", "cohort_shifted", "half_eval")


def faults_for(name: str):
    """The faults cell ``name`` can show: the eval's only where it compares it."""
    limits = chipbench_tiny.tiny_cell(name)["limits"]
    return [f for f in FAULTS if f != "half_eval" or "eval_gap" in limits]


def _unchanged(loss_fn, params, batches, **kw):
    res = local_train(loss_fn, params, batches, **kw)
    return LocalResult(params=params, mean_loss=res.mean_loss, last_loss=res.last_loss,
                       update_sqnorm=res.update_sqnorm)


def _half_batch(loss_fn, params, batches, **kw):
    import jax

    half = jax.tree_util.tree_map(lambda x: x[:, : x.shape[1] // 2], batches)
    return local_train(loss_fn, params, half, **kw)


def _half_eval(model, params, batch):
    import jax

    half = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
    return _default_eval(model, params, half)


def _shifted_selector(make):
    import jax.numpy as jnp

    def factory(*a, **k):
        select = make(*a, **k)

        def shifted(key, state, t):
            mask, probs = select(key, state, t)
            return jnp.roll(mask, 1), probs

        return shifted

    return factory


def run_with_fault(name: str, fault: str, monkeypatch):
    """The result line of a tiny run of cell ``name`` with ``fault`` planted."""
    import run

    if fault == "unchanged":
        monkeypatch.setattr(fed_batched, "local_train", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(fed_batched, "local_train", _half_batch)
    elif fault == "half_eval":
        monkeypatch.setattr(fed_engine, "default_eval", _half_eval)
    elif fault == "cohort_shifted":
        monkeypatch.setattr(fed_engine, "make_selector",
                            _shifted_selector(fed_engine.make_selector))
    return run.run_cell(chipbench_tiny.tiny_cell(name), seed=2 ** 31 + 77, seconds=3.0,
                        trace=False, require_tpu=False)
