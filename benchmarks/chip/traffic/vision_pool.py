"""Traffic kind ``vision_pool``: a finite image pool dealt out to K clients
by Dirichlet label skew (the paper's federation)."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

import jax

from traffic.generate import GATHER_SPAN, _Recorder, class_templates, client_label_js, dirichlet_partition


class VisionPool(_Recorder):
    """Paper-scale federation: a concrete image pool, per-client index lists."""

    def __init__(self, p: Dict[str, Any], seed: int) -> None:
        super().__init__()
        rng = np.random.default_rng(seed)
        c, size = p["num_classes"], p["image_size"]
        templates = class_templates(rng, c, size)

        def sample(n_per_class: int):
            labels = np.repeat(np.arange(c), n_per_class)
            imgs = templates[labels] + p["noise"] * rng.normal(
                size=(len(labels), size, size, 3))
            return imgs.astype(np.float32), labels.astype(np.int32)

        self.images, self.labels = sample(p["train_per_class"])
        self.test_images, self.test_labels = sample(p["test_per_class"])
        self.client_indices, self.label_dists = dirichlet_partition(
            self.labels, p["num_clients"], p["dirichlet_alpha"], seed)
        self.label_js = client_label_js(self.label_dists)

    @property
    def num_clients(self) -> int:
        return len(self.client_indices)

    def _batches(self, k: int, steps: int, batch: int, rng) -> Dict[str, np.ndarray]:
        pick = rng.choice(self.client_indices[k], size=(steps, batch), replace=True)
        return {"images": self.images[pick], "labels": self.labels[pick]}

    def client_batches(self, k: int, steps: int, batch: int, rng) -> Dict[str, jax.Array]:
        self._note("client", (int(k), steps, batch), rng)
        with jax.profiler.TraceAnnotation(GATHER_SPAN):
            return self._device(self._batches(int(k), steps, batch, rng))

    def replay(self, entry) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """(client ids (n,), batches with leaves (n, steps, batch, ...))."""
        _, (k, steps, batch), state = entry
        b = self._batches(k, steps, batch, self._rng_at(state))
        return np.asarray([k]), {n: v[None] for n, v in b.items()}

    def eval_batch(self) -> Dict[str, jax.Array]:
        return self._device({"images": self.test_images, "labels": self.test_labels})


def build(params: Dict[str, Any], seed: int) -> VisionPool:
    return VisionPool(params, seed)
