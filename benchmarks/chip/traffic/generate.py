"""The benchmark's traffic: seeded federated datasets, one module per kind.

A cell's workload file names a ``kind`` and its parameters; ``build`` finds
``traffic/<kind>.py`` by that name and turns the parameters and ``--seed``
into the data object the federated engine consumes (``num_clients``,
``label_js``, ``eval_batch()`` and ``client_batches`` or
``stacked_client_batches``). A new kind is a new module with a
``build(params, seed)``; nothing here changes. The kinds so far are copies
of the sound generators the program ships in ``repro.data.synthetic``
(Dirichlet-pool vision data, lazily synthesized cross-device vision data,
per-client bigram token streams), kept here so that a change to the
program's generators does not move the yardstick. This module holds what
they share.

While ``recording`` is on, every batch request logs the host RNG state it
started from, so the reference can replay exactly the batches the timed
path trained on (``replay``). The harness turns recording off after the
rounds it compares. Each request runs under a ``bench.gather`` profiler
annotation: host synthesis plus the host-to-device copy.
"""

from __future__ import annotations

import copy
import importlib
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

GATHER_SPAN = "bench.gather"


# ---------------------------------------------------------------------------
# Label skew (copied from repro.fed.partition)
# ---------------------------------------------------------------------------


def js_divergence(p: np.ndarray, q: np.ndarray, eps: float = 1e-12) -> np.ndarray:
    """Jensen-Shannon divergence (base e, in [0, log 2]); broadcasts over rows."""
    p = np.asarray(p, dtype=np.float64) + eps
    q = np.asarray(q, dtype=np.float64) + eps
    p = p / p.sum(axis=-1, keepdims=True)
    q = q / q.sum(axis=-1, keepdims=True)
    m = 0.5 * (p + q)
    kl_pm = np.sum(p * np.log(p / m), axis=-1)
    kl_qm = np.sum(q * np.log(q / m), axis=-1)
    return 0.5 * (kl_pm + kl_qm)


def client_label_js(dists: np.ndarray) -> np.ndarray:
    """JS(P_k || P_avg) for every client."""
    return js_divergence(dists, dists.mean(axis=0, keepdims=True))


def dirichlet_partition(labels: np.ndarray, num_clients: int, alpha: float,
                        seed: int, min_per_client: int = 8
                        ) -> Tuple[List[np.ndarray], np.ndarray]:
    """Per-client index arrays and (K, C) label distributions under Dir(alpha)
    label skew; re-draws until every client holds min_per_client samples."""
    rng = np.random.default_rng(seed)
    classes = np.unique(labels)
    num_classes = len(classes)
    for _ in range(100):
        props = rng.dirichlet(np.full(num_classes, alpha), size=num_clients)
        client_idx: List[List[int]] = [[] for _ in range(num_clients)]
        for ci, c in enumerate(classes):
            idx = np.flatnonzero(labels == c)
            rng.shuffle(idx)
            w = props[:, ci] / max(props[:, ci].sum(), 1e-12)
            counts = np.floor(w * len(idx)).astype(int)
            counts[-1] = len(idx) - counts[:-1].sum()
            start = 0
            for k in range(num_clients):
                client_idx[k].extend(idx[start:start + counts[k]])
                start += counts[k]
        if min(len(ix) for ix in client_idx) >= min_per_client:
            break
    out = [np.array(sorted(ix), dtype=np.int64) for ix in client_idx]
    dists = np.zeros((num_clients, num_classes))
    for k, ix in enumerate(out):
        if len(ix):
            binc = np.bincount(labels[ix].astype(int), minlength=num_classes)
            dists[k] = binc / binc.sum()
    return out, dists


def class_templates(rng: np.random.Generator, num_classes: int, size: int) -> np.ndarray:
    """Smooth class templates: low-frequency random fields, upsampled 4x."""
    low = rng.normal(size=(num_classes, size // 4, size // 4, 3))
    up = np.repeat(np.repeat(low, 4, axis=1), 4, axis=2)
    return up / np.abs(up).max(axis=(1, 2, 3), keepdims=True)


# ---------------------------------------------------------------------------
# Recording
# ---------------------------------------------------------------------------


class _Recorder:
    """Logs (request, RNG state) while ``recording``; replays on demand."""

    def __init__(self) -> None:
        self.recording = False
        self.log: List[Tuple[str, Any, Dict[str, Any]]] = []

    def _note(self, what: str, args: Any, rng: np.random.Generator) -> None:
        if self.recording:
            self.log.append((what, args, copy.deepcopy(rng.bit_generator.state)))

    @staticmethod
    def _rng_at(state: Dict[str, Any]) -> np.random.Generator:
        rng = np.random.default_rng()
        rng.bit_generator.state = copy.deepcopy(state)
        return rng

    @staticmethod
    def _device(batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        return {k: jnp.asarray(v) for k, v in batch.items()}


def build(traffic: Dict[str, Any], seed: int):
    """The data object for one cell's traffic parameters and seed."""
    kind = traffic["kind"]
    try:
        module = importlib.import_module(f"traffic.{kind}")
    except ModuleNotFoundError as e:
        raise ValueError(f"unknown traffic kind {kind!r}: no traffic/{kind}.py") from e
    return module.build(traffic, seed)
