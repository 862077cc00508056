"""Traffic kind ``vision_lazy``: K clients that exist only as label
distributions; each round's cohort is synthesized on demand (cross-device
scale)."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

import jax

from traffic.generate import GATHER_SPAN, _Recorder, class_templates, client_label_js


class VisionLazy(_Recorder):
    """Cross-device federation: each round's cohort is synthesized on demand
    (labels from the client's Dir(alpha) mix, pixels from class templates
    plus noise), one vectorized pass for the whole cohort."""

    def __init__(self, p: Dict[str, Any], seed: int) -> None:
        super().__init__()
        rng = np.random.default_rng(seed)
        c, size = p["num_classes"], p["image_size"]
        self.noise = p["noise"]
        self.templates = class_templates(rng, c, size).astype(np.float32)
        self.label_dists = rng.dirichlet(
            np.full(c, p["dirichlet_alpha"]), size=p["num_clients"]).astype(np.float64)
        self.label_js = client_label_js(self.label_dists)
        self.test_labels = np.repeat(np.arange(c), p["test_per_class"]).astype(np.int32)
        self.test_images = (self.templates[self.test_labels] + self.noise * rng.standard_normal(
            (len(self.test_labels), size, size, 3), dtype=np.float32)).astype(np.float32)

    @property
    def num_clients(self) -> int:
        return self.label_dists.shape[0]

    def _batches(self, sel: np.ndarray, steps: int, batch: int, rng) -> Dict[str, np.ndarray]:
        m, n = len(sel), steps * batch
        cdf = np.cumsum(self.label_dists[np.asarray(sel, np.int64)], axis=1)
        labels = (rng.random((m, n, 1)) > cdf[:, None, :]).sum(axis=2).astype(np.int32)
        imgs = (self.templates[labels] + self.noise * rng.standard_normal(
            labels.shape + self.templates.shape[1:], dtype=np.float32)).astype(np.float32)
        h, w = self.templates.shape[1], self.templates.shape[2]
        return {"images": imgs.reshape(m, steps, batch, h, w, 3),
                "labels": labels.reshape(m, steps, batch)}

    def stacked_client_batches(self, selected, steps: int, batch: int, rng) -> Dict[str, jax.Array]:
        sel = np.asarray(selected).copy()
        self._note("cohort", (sel, steps, batch), rng)
        with jax.profiler.TraceAnnotation(GATHER_SPAN):
            return self._device(self._batches(sel, steps, batch, rng))

    def replay(self, entry) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        _, (sel, steps, batch), state = entry
        return sel, self._batches(sel, steps, batch, self._rng_at(state))

    def eval_batch(self) -> Dict[str, jax.Array]:
        return self._device({"images": self.test_images, "labels": self.test_labels})


def build(params: Dict[str, Any], seed: int) -> VisionLazy:
    return VisionLazy(params, seed)
