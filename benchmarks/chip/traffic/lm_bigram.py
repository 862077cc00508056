"""Traffic kind ``lm_bigram``: per-client "dialect" token streams
(cross-silo language-model federation)."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

import jax

from traffic.generate import GATHER_SPAN, _Recorder, js_divergence


class LMBigram(_Recorder):
    """Cross-silo LM federation: each silo's tokens follow its own affine
    bigram rule (next = a*prev + b mod vocab) with 10% uniform noise."""

    def __init__(self, p: Dict[str, Any], seed: int) -> None:
        super().__init__()
        rng = np.random.default_rng(seed)
        k = p["num_clients"]
        self.vocab, self.seq_len = p["vocab"], p["seq_len"]
        self.eval_sequences = p["eval_sequences"]
        a = rng.choice([3, 5, 7, 11, 13, 17, 19, 23], size=k)
        b = rng.integers(0, self.vocab, size=k)
        self.rules = np.stack([a, b], axis=1)
        # Diversity proxy: JS of each rule's unigram histogram (mod 64).
        hists = np.zeros((k, min(self.vocab, 64)))
        for c in range(k):
            s = self._sample(c, 8, np.random.default_rng(c))
            hists[c] = np.bincount(s.ravel() % hists.shape[1], minlength=hists.shape[1])
        hists = hists / hists.sum(axis=1, keepdims=True)
        self.label_js = js_divergence(hists, hists.mean(axis=0, keepdims=True))
        self.eval_seed = int(rng.integers(0, 2**31 - 1))

    @property
    def num_clients(self) -> int:
        return len(self.rules)

    def _sample(self, k: int, n: int, rng) -> np.ndarray:
        a, b = self.rules[k]
        toks = np.empty((n, self.seq_len), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=n)
        noise = rng.random((n, self.seq_len)) < 0.1
        rand = rng.integers(0, self.vocab, size=(n, self.seq_len))
        for t in range(1, self.seq_len):
            nxt = (toks[:, t - 1].astype(np.int64) * a + b) % self.vocab
            toks[:, t] = np.where(noise[:, t], rand[:, t], nxt)
        return toks

    def _batches(self, k: int, steps: int, batch: int, rng) -> Dict[str, np.ndarray]:
        toks = self._sample(k, steps * batch, rng).reshape(steps, batch, self.seq_len)
        return {"tokens": toks, "labels": toks}

    def client_batches(self, k: int, steps: int, batch: int, rng) -> Dict[str, jax.Array]:
        self._note("client", (int(k), steps, batch), rng)
        with jax.profiler.TraceAnnotation(GATHER_SPAN):
            return self._device(self._batches(int(k), steps, batch, rng))

    def replay(self, entry) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        _, (k, steps, batch), state = entry
        b = self._batches(k, steps, batch, self._rng_at(state))
        return np.asarray([k]), {n: v[None] for n, v in b.items()}

    def eval_batch(self) -> Dict[str, jax.Array]:
        rng = np.random.default_rng(self.eval_seed)
        per = max(self.eval_sequences // self.num_clients, 1)
        toks = np.concatenate([self._sample(k, per, rng) for k in range(self.num_clients)])
        return self._device({"tokens": toks, "labels": toks})


def build(params: Dict[str, Any], seed: int) -> LMBigram:
    return LMBigram(params, seed)
