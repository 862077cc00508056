"""Composable federated round engine (paper Algorithm 1 as a plugin surface).

``FederatedEngine`` owns the Algorithm-1 skeleton — score → select → local
train → aggregate → metadata update → eval — and delegates each stage to a
protocol-typed plugin, so new execution engines, wire codecs, aggregation
rules and cross-cutting behaviours land without touching the loop:

  * ``ClientExecutor`` — how the selected cohort trains. ``BatchedExecutor``
    (one vmapped jitted call, ``fed.batched``), ``SequentialExecutor`` (one
    jitted call per client — the numerical reference), and
    ``CompressedExecutor`` (wraps either and owns the codec state: per-client
    error-feedback residuals for top-k, stacked per-cohort quantization for
    int8). Executors return a ``CohortUpdates``.
  * ``Aggregator`` — how the cohort's updates become the next global model:
    ``FedAvg`` (Alg. 1 line 26), ``WeightedFedAvg`` (|D_k|-weighted McMahan
    form), ``FedAvgM`` (server momentum). Aggregators may provide cohort
    weights up front so the batched path can fold them into its fused
    reduction (``fed.server.fedavg_fused``) instead of re-materializing the
    client stack.
  * ``RoundHook`` — cross-cutting callbacks around the loop: metrics
    collection (``MetricsHook``), verbose logging (``VerboseHook``),
    Lemma-A.4 μ retuning (``AdaptiveMuHook``), and mid-run checkpoint/resume
    (``CheckpointHook``, backed by ``repro.ckpt``).

Configuration is one ``FederatedSpec`` builder: registry-backed
``executor=`` / ``aggregator=`` / ``hooks=`` names (or instances), replacing
the grown-by-accretion keyword surface of the old ``run_federated`` monolith
— which survives in ``fed.loop`` as a thin wrapper that assembles a spec and
returns the same ``FLResult``.

Numerics contract: with the same seeds and plugins, the engine consumes the
host/device RNG streams in exactly the order the pre-refactor loop did, so
``run_federated`` results are unchanged (tests/test_engine_api.py pins this
against golden metrics captured pre-refactor).
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import (
    Any,
    Callable,
    Dict,
    List,
    Optional,
    Protocol,
    Sequence,
    Tuple,
    Union,
    runtime_checkable,
)

import numpy as np

import jax
import jax.numpy as jnp

from repro import ckpt as repro_ckpt
from repro.configs.base import FedConfig
from repro.core.adaptive import AdaptiveMu
from repro.core.scoring import HeteRoScoreConfig
from repro.core.selection import SelectorConfig, make_selector
from repro.core.state import (
    ClientState,
    init_client_state,
    scatter_observations,
    to_bf16,
    update_client_state,
)
from repro.fed import availability as fed_avail
from repro.fed import batched as fed_batched
from repro.fed import client as fed_client
from repro.fed import compression as fed_comp
from repro.fed import server as fed_server
from repro.obs.metrics import MetricsRegistry
from repro.obs.phases import current_recorder, phase, round_step
from repro.obs.trace import NULL_TRACER
from repro.sharding.rules import MeshAxes, axis_size

EvalFn = Callable[..., float]  # (model, params, eval_batch) -> scalar metric


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FLResult:
    """Everything the paper reports for one federated run.

    ``accuracy`` holds the per-round eval metric; ``metric_name`` says what
    that metric actually is — ``"accuracy"`` for classifiers, the
    perplexity-derived ``"exp(-loss)"`` for LM families — so summaries and
    logs stop labelling LM numbers as accuracy.
    """

    accuracy: np.ndarray          # (rounds,) per-round eval metric
    train_loss: np.ndarray        # (rounds,)
    selection_counts: np.ndarray  # (K,)
    selected_history: np.ndarray  # (rounds, K) bool
    params: Any
    wire_bytes: int = 0           # client→server traffic (compression on)
    raw_bytes: int = 0
    mu_history: Optional[np.ndarray] = None  # adaptive-μ trace
    metric_name: str = "accuracy"
    # Async-mode extras (fed.async_engine): virtual close time of each round
    # and the mean staleness of the updates aggregated in it. None for sync
    # runs, where every round costs "1" and staleness is always 0.
    wall_clock: Optional[np.ndarray] = None
    round_staleness: Optional[np.ndarray] = None
    # Hierarchical-mode extra (fed.hierarchy): edge aggregates uploaded to
    # the cloud per round — the WAN communication axis benchmarks/
    # table7_hierarchy.py compares against flat selection. None for flat
    # runs, where every selected client uploads straight to the cloud.
    cloud_uploads: Optional[np.ndarray] = None
    # Per-round host-observed phase timings (ms): cohort selection, local
    # training (executor), and aggregation. Persisted in round snapshots
    # since format v3, so resumed runs report the prefix's real wall times.
    # The selection axis is what benchmarks/table8_selector.py scales to
    # K=10⁶.
    select_ms: Optional[np.ndarray] = None
    execute_ms: Optional[np.ndarray] = None
    aggregate_ms: Optional[np.ndarray] = None
    # Serving-tier extras (repro.serve.PublishHook): a summary dict of the
    # request telemetry (latency percentiles, decode throughput, served
    # version lag, publish/hot-swap cost) and the accuracy-under-traffic
    # series — per-round eval of the version being *served*, which trails
    # the freshly aggregated params by one round (serve v while v+1
    # aggregates). ``served_metric`` rows are (round, version, metric).
    # None unless a PublishHook was attached.
    serving: Optional[Dict[str, Any]] = None
    served_metric: Optional[np.ndarray] = None
    # Telemetry-plane extra (repro.obs.TelemetryHook): the JSON-able
    # registry snapshot (per-round fairness/communication rows, counters,
    # histogram summaries) plus the stability summary and any artifact
    # paths. None unless a TelemetryHook was attached.
    telemetry: Optional[Dict[str, Any]] = None

    @property
    def peak_acc(self) -> float:
        return float(self.accuracy.max())

    @property
    def final_acc(self) -> float:
        return float(self.accuracy[-1])

    @property
    def stable_acc(self) -> float:
        return float(self.accuracy[-10:].mean())

    @property
    def stability_drop(self) -> float:
        return self.peak_acc - self.final_acc

    @property
    def selection_std(self) -> float:
        return float(self.selection_counts.std())

    def summary(self) -> Dict[str, float]:
        return {
            "peak_acc": self.peak_acc,
            "final_acc": self.final_acc,
            "stable_acc": self.stable_acc,
            "stability_drop": self.stability_drop,
            "selection_std": self.selection_std,
        }

    def labeled_summary(self) -> Dict[str, float]:
        """``summary()`` with the eval metric named honestly in the keys."""
        m = self.metric_name
        return {
            f"peak_{m}": self.peak_acc,
            f"final_{m}": self.final_acc,
            f"stable_{m}": self.stable_acc,
            "stability_drop": self.stability_drop,
            "selection_std": self.selection_std,
        }


def default_eval(model: Any, params: Any, batch: Dict[str, jnp.ndarray]) -> float:
    """Accuracy for classifiers; exp(-loss) (per-token) for LM families."""
    if model.cfg.family == "resnet":
        logits = model.forward(params, batch)
        return float(jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32)))
    loss = model.loss(params, batch)
    return float(jnp.exp(-loss))


def default_metric_name(model: Any) -> str:
    return "accuracy" if model.cfg.family == "resnet" else "exp(-loss)"


# ---------------------------------------------------------------------------
# Stage protocols + cohort container
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CohortUpdates:
    """One round's cohort outcome, in whichever layout the executor produced.

    Exactly one of ``avg_params`` / ``param_list`` / ``delta_list`` is
    required for aggregation: the batched engine ships the fused weighted
    mean (plus, optionally, the (M, ...) client stack), the sequential
    engine a Python list in cohort order. ``mean_loss`` / ``update_sqnorm``
    are (M,) in cohort order — jax arrays from the batched path, numpy from
    sequential.

    The async engine aggregates *arrivals*, not cohorts: ``delta_list``
    carries per-update parameter deltas Δ_i = w_i − w_anchor(i), each
    relative to the global version its client trained on, and ``staleness``
    the (M,) model-version lag of each update at aggregation time (0 for
    updates landing in their own dispatch round). Staleness-aware
    aggregators (``BufferedAggregator``) consume both.
    """

    mean_loss: Any
    update_sqnorm: Any
    avg_params: Optional[Any] = None
    param_list: Optional[List[Any]] = None
    stacked_params: Optional[Any] = None
    weights: Optional[Any] = None  # the aggregator-provided cohort weights
    wire_bytes: int = 0
    raw_bytes: int = 0
    delta_list: Optional[List[Any]] = None  # async: per-update deltas
    staleness: Optional[np.ndarray] = None  # async: (M,) version lag


@runtime_checkable
class ClientExecutor(Protocol):
    """How the selected cohort trains for one round.

    ``kind`` names the execution schedule ('batched' | 'sequential' | the
    wrapped kind for decorating executors); ``set_mu`` rebinds the FedProx
    coefficient (recompile — rare, driven by ``AdaptiveMuHook``).
    """

    kind: str

    def run_round(self, params: Any, selected: np.ndarray,
                  rng: np.random.Generator,
                  weights: Optional[jax.Array] = None) -> CohortUpdates: ...

    def set_mu(self, mu: float) -> None: ...


class Aggregator:
    """How cohort updates become the next global model (Alg. 1 line 26).

    ``cohort_weights`` runs *before* execution so the batched path can fold
    the weights into its fused reduction; ``reduce`` turns the cohort into
    the new global params. ``get_state``/``set_state`` expose optional
    server-side state (e.g. momentum velocity) to ``CheckpointHook``.
    """

    name = "base"
    # Whether reduce() understands delta-form cohorts (delta_list +
    # staleness) — required by the async engine, whose arrivals are deltas
    # against *different* global versions and cannot be plainly averaged.
    supports_deltas = False

    def cohort_weights(self, selected: np.ndarray, data: Any) -> Optional[jax.Array]:
        return None

    def reduce(self, global_params: Any, cohort: CohortUpdates) -> Any:
        raise NotImplementedError

    def get_state(self) -> Optional[Any]:
        return None

    def set_state(self, state: Any) -> None:
        pass

    def _mean(self, cohort: CohortUpdates) -> Any:
        if cohort.avg_params is not None:
            return cohort.avg_params
        if cohort.param_list is None:
            raise ValueError("cohort carries neither avg_params nor param_list")
        if cohort.weights is not None:
            return fed_server.fedavg_weighted(cohort.param_list,
                                              np.asarray(cohort.weights))
        return fed_server.fedavg(cohort.param_list)


class RoundHook:
    """Cross-cutting round-loop callback. Subclass and override what you need.

    Call order per run: ``on_run_start`` (may restore a checkpoint into the
    engine), then per round ``on_round_start`` / ``on_round_end``, then
    ``on_run_end`` and ``contribute`` (write extra fields — e.g.
    ``mu_history`` — into the result)."""

    # Hook-list ordering weight: higher runs earlier. ``_resolve_hooks``
    # stable-sorts on it (MetricsHook is pinned to slot 0 regardless), so
    # producers like TelemetryHook (priority 10) deterministically precede
    # consumers like VerboseHook — resumed runs rebuild the same order,
    # which position-keyed checkpoint hook state depends on.
    priority = 0

    def on_run_start(self, ctx: "RoundContext") -> None:
        pass

    def on_round_start(self, ctx: "RoundContext") -> None:
        pass

    def on_round_end(self, ctx: "RoundContext") -> None:
        pass

    def on_run_end(self, ctx: "RoundContext") -> None:
        pass

    def contribute(self, extras: Dict[str, Any]) -> None:
        pass

    def state_dict(self) -> Optional[Dict[str, Any]]:
        """JSON-able resumable state, or None. ``CheckpointHook`` persists it
        (keyed by hook-list position — resumed runs must rebuild the same
        hook list) and feeds it back through ``load_state_dict``."""
        return None

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        pass


@dataclasses.dataclass
class RoundContext:
    """What hooks see. Mutated in place by the engine as the round advances."""

    engine: "FederatedEngine"
    round_idx: int = 0
    mask: Optional[np.ndarray] = None       # (K,) bool — this round's cohort
    selected: Optional[np.ndarray] = None   # cohort client ids
    obs_loss: Optional[np.ndarray] = None   # (K,) dense observations
    obs_sqnorm: Optional[np.ndarray] = None
    metric: float = 0.0                     # this round's eval metric
    train_loss: float = 0.0
    # Virtual time at this round's close. Async engines report the clock;
    # sync rounds cost "1" each, so the close lands on ``t + 1`` — giving
    # hooks (the serving tier's PublishHook in particular) one uniform time
    # axis under every round_policy. ``num_arrivals``/``num_stragglers``
    # stay 0 in sync runs.
    sim_time: float = 0.0
    num_arrivals: int = 0
    num_stragglers: int = 0
    # Host-observed phase timings of this round, in milliseconds.
    select_ms: float = 0.0
    execute_ms: float = 0.0
    aggregate_ms: float = 0.0

    @property
    def fed(self) -> FedConfig:
        return self.engine.spec.fed

    @property
    def params(self) -> Any:
        return self.engine.params


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

EXECUTORS: Dict[str, Callable[["FederatedSpec"], ClientExecutor]] = {}
AGGREGATORS: Dict[str, Callable[["FederatedSpec"], Aggregator]] = {}
HOOKS: Dict[str, Callable[["FederatedSpec"], RoundHook]] = {}


def register_executor(name: str):
    def deco(factory):
        EXECUTORS[name] = factory
        return factory
    return deco


def register_aggregator(name: str):
    def deco(factory):
        AGGREGATORS[name] = factory
        return factory
    return deco


def register_hook(name: str):
    def deco(factory):
        HOOKS[name] = factory
        return factory
    return deco


# ---------------------------------------------------------------------------
# Executors
# ---------------------------------------------------------------------------


class BatchedExecutor:
    """Whole cohort in one vmapped jitted call (``fed.batched``).

    Honors ``FedConfig.client_chunk`` (fixed-shape chunks, bounded memory)
    and pod-mesh sharding. ``keep_client_params=True`` additionally returns
    the (M, ...) client stack — required by codecs that re-aggregate — and
    is incompatible with chunking (the stack never materializes there)."""

    kind = "batched"

    def __init__(self, spec: "FederatedSpec", keep_client_params: bool = False):
        self.model = spec.model
        self.fed = spec.fed
        self.data = spec.data
        self.steps = spec.resolved_steps
        self.mesh = spec.mesh
        self.mesh_axes = spec.mesh_axes
        self.keep_client_params = keep_client_params
        self.pod_size = 0
        if spec.mesh is not None and spec.mesh_axes is not None \
                and spec.mesh_axes.pod is not None:
            self.pod_size = axis_size(spec.mesh, spec.mesh_axes.pod)
        self.set_mu(spec.fed.mu)

    def set_mu(self, mu: float) -> None:
        self._train = fed_batched.make_batched_local_train(
            self.model.loss, lr=self.fed.lr, mu=mu,
            mesh=self.mesh, axes=self.mesh_axes)

    def run_round(self, params, selected, rng, weights=None) -> CohortUpdates:
        with phase("gather", recorder=current_recorder()):
            stacked = fed_batched.gather_stacked_batches(
                self.data, selected, self.steps, self.fed.local_batch, rng)
        if self.pod_size > 1 and len(selected) % self.pod_size == 0:
            # Place the cohort client-sharded over 'pod' up front, so the
            # shard_map step takes it as is (no reshard on entry).
            stacked = fed_batched.shard_cohort(stacked, self.mesh,
                                               self.mesh_axes)
        cohort = fed_batched.train_clients_batched(
            self._train, params, stacked, weights=weights,
            chunk=self.fed.client_chunk, pad_to=self.pod_size,
            keep_client_params=self.keep_client_params)
        return CohortUpdates(
            mean_loss=cohort.mean_loss,
            update_sqnorm=cohort.update_sqnorm,
            avg_params=cohort.avg_params,
            stacked_params=cohort.stacked_params,
            weights=weights,
        )


class SequentialExecutor:
    """One jitted ``local_train`` call per client — the numerical reference."""

    kind = "sequential"

    def __init__(self, spec: "FederatedSpec"):
        self.model = spec.model
        self.fed = spec.fed
        self.data = spec.data
        self.steps = spec.resolved_steps
        self.set_mu(spec.fed.mu)

    def set_mu(self, mu: float) -> None:
        self._train = jax.jit(functools.partial(
            fed_client.local_train, self.model.loss, lr=self.fed.lr, mu=mu))

    def run_round(self, params, selected, rng, weights=None) -> CohortUpdates:
        m = len(selected)
        param_list: List[Any] = []
        losses = np.zeros(m, np.float32)
        sqnorms = np.zeros(m, np.float32)
        recorder = current_recorder()
        for i, k in enumerate(selected):
            with phase("gather", recorder=recorder):
                batches = self.data.client_batches(
                    int(k), self.steps, self.fed.local_batch, rng)
            res = self._train(params, batches)
            losses[i] = float(res.mean_loss)
            sqnorms[i] = float(res.update_sqnorm)
            param_list.append(res.params)
        return CohortUpdates(
            mean_loss=losses,
            update_sqnorm=sqnorms,
            param_list=param_list,
            weights=weights,
        )


class ExecutorCompatError(ValueError):
    """A codec / execution-schedule combination that cannot work."""


class CompressedExecutor:
    """Wire-compression decorator around any executor (paper Sec II-B).

    Compresses each client's delta Δ = w_k − w_global with the configured
    codec, immediately decodes it (simulating the client→server wire), and
    re-exposes the cohort in the standard layout, so ANY aggregator composes
    downstream. Owns all codec state:

      * ``'int8'`` — stateless per-tensor quantization. Composes with the
        batched schedule: quantization runs vectorized over the (M, ...)
        client stack (``fed.compression.quantize_int8_stacked``).
      * ``'topk'`` — top-k sparsification with error feedback. The
        per-client residuals live here (``self.residuals``), keyed by client
        id; they are host-side state, so this codec requires the sequential
        schedule and construction raises ``ExecutorCompatError`` otherwise —
        never a silent engine switch.

    Incompatibilities are loud: int8 over a chunked/pod-padded batched
    executor (the client stack never materializes) also raises."""

    def __init__(self, inner: ClientExecutor, codec: str, topk_frac: float = 0.1):
        if codec not in ("int8", "topk"):
            raise ValueError(f"unknown compression codec {codec!r}")
        if codec == "topk" and inner.kind != "sequential":
            raise ExecutorCompatError(
                "compression='topk' keeps per-client host-side error-feedback "
                "residuals and requires the sequential executor; got "
                f"{inner.kind!r}. Pass client_execution='sequential' (or an "
                "explicit SequentialExecutor).")
        if codec == "int8" and inner.kind == "batched":
            if inner.fed.client_chunk:
                raise ExecutorCompatError(
                    "compression='int8' over the batched executor needs the "
                    "full (M, ...) client stack, which chunked execution "
                    "(FedConfig.client_chunk > 0) never materializes; set "
                    "client_chunk=0 or use the sequential executor.")
            if getattr(inner, "pod_size", 0) > 1:
                raise ExecutorCompatError(
                    "compression='int8' over a pod-sharded batched executor "
                    "is not supported yet (padded cohorts re-route through "
                    "the chunk path); use the sequential executor.")
            inner.keep_client_params = True
        self.inner = inner
        self.kind = inner.kind
        self.codec = codec
        self.topk_frac = topk_frac
        self.residuals: Dict[int, Any] = {}

    def set_mu(self, mu: float) -> None:
        self.inner.set_mu(mu)

    def run_round(self, params, selected, rng, weights=None) -> CohortUpdates:
        cohort = self.inner.run_round(params, selected, rng, weights=weights)
        if cohort.param_list is not None:
            return self._compress_list(params, selected, cohort)
        return self._compress_stacked(params, cohort)

    def _compress_list(self, anchor, selected, cohort: CohortUpdates) -> CohortUpdates:
        wire = raw = 0
        rebuilt: List[Any] = []
        for i, k in enumerate(selected):
            delta = fed_comp.tree_delta(cohort.param_list[i], anchor)
            if self.codec == "int8":
                c, stats = fed_comp.quantize_int8(delta)
                decoded = fed_comp.dequantize_int8(c)
            else:
                c, resid, stats = fed_comp.topk_sparsify(
                    delta, self.topk_frac, self.residuals.get(int(k)))
                self.residuals[int(k)] = resid
                decoded = fed_comp.desparsify(c)
            wire += stats.wire_bytes
            raw += stats.raw_bytes
            rebuilt.append(fed_comp.tree_apply_delta(anchor, decoded))
        return dataclasses.replace(
            cohort, param_list=rebuilt, wire_bytes=wire, raw_bytes=raw)

    def _compress_stacked(self, anchor, cohort: CohortUpdates) -> CohortUpdates:
        if cohort.stacked_params is None:
            raise ExecutorCompatError(
                "batched executor returned no client stack to compress "
                "(keep_client_params was not honoured)")
        delta = fed_comp.tree_delta(cohort.stacked_params, anchor)  # broadcasts
        c, stats = fed_comp.quantize_int8_stacked(delta)
        decoded = fed_comp.dequantize_int8_stacked(c)
        rebuilt = fed_comp.tree_apply_delta(anchor, decoded)
        with phase("aggregate"):
            avg = fed_server.fedavg_fused(rebuilt, cohort.weights)
        return dataclasses.replace(
            cohort, avg_params=avg, stacked_params=rebuilt,
            wire_bytes=stats.wire_bytes, raw_bytes=stats.raw_bytes)


@register_executor("batched")
def _make_batched(spec: "FederatedSpec") -> BatchedExecutor:
    return BatchedExecutor(spec)


@register_executor("sequential")
def _make_sequential(spec: "FederatedSpec") -> SequentialExecutor:
    return SequentialExecutor(spec)


# ---------------------------------------------------------------------------
# Aggregators
# ---------------------------------------------------------------------------


class FedAvg(Aggregator):
    """Unweighted mean over the cohort — the paper's Algorithm 1 line 26."""

    name = "fedavg"

    def reduce(self, global_params, cohort):
        return self._mean(cohort)


class WeightedFedAvg(Aggregator):
    """|D_k|-weighted FedAvg (the original McMahan form).

    Weights default to per-client example counts when the data source
    exposes them (``client_indices`` lengths or a ``client_sizes`` array),
    else uniform. The batched path folds the weights into its fused
    reduction; the sequential path applies them list-wise."""

    name = "fedavg_weighted"

    def __init__(self, weight_fn: Optional[Callable[[np.ndarray, Any], np.ndarray]] = None):
        self.weight_fn = weight_fn
        self._sizes: Optional[np.ndarray] = None  # per-run cache, O(K) once

    def cohort_weights(self, selected, data):
        if self.weight_fn is not None:
            return jnp.asarray(self.weight_fn(selected, data), jnp.float32)
        if self._sizes is None:
            sizes = getattr(data, "client_sizes", None)
            if sizes is None and getattr(data, "client_indices", None) is not None:
                sizes = [len(ix) for ix in data.client_indices]
            if sizes is None:
                sizes = np.ones(data.num_clients)  # uniform fallback
            self._sizes = np.asarray(sizes, np.float32)
        return jnp.asarray(self._sizes[selected])

    def reduce(self, global_params, cohort):
        return self._mean(cohort)


class FedAvgM(Aggregator):
    """FedAvgM: server momentum over the round means (``fed.server``)."""

    name = "fedavgm"

    def __init__(self, beta: float = 0.9):
        self.momentum = fed_server.ServerMomentum(beta=beta)

    def reduce(self, global_params, cohort):
        return self.momentum.apply(global_params, self._mean(cohort))

    def get_state(self):
        return self.momentum.velocity

    def set_state(self, state):
        self.momentum.velocity = state


@register_aggregator("fedavg")
def _make_fedavg(spec: "FederatedSpec") -> FedAvg:
    return FedAvg()


@register_aggregator("fedavg_weighted")
def _make_weighted(spec: "FederatedSpec") -> WeightedFedAvg:
    return WeightedFedAvg()


@register_aggregator("fedavgm")
def _make_fedavgm(spec: "FederatedSpec") -> FedAvgM:
    return FedAvgM()


# ---------------------------------------------------------------------------
# Hooks
# ---------------------------------------------------------------------------


class MetricsHook(RoundHook):
    """Collects the per-round series ``FLResult`` is built from.

    The engine installs one automatically (first in the hook list) when the
    spec does not provide one; subclass it to collect more without touching
    the loop."""

    def __init__(self):
        self.metric: List[float] = []
        self.train_loss: List[float] = []
        self.selected: List[np.ndarray] = []
        self.select_ms: List[float] = []
        self.execute_ms: List[float] = []
        self.aggregate_ms: List[float] = []

    def reset(self) -> None:
        self.metric, self.train_loss, self.selected = [], [], []
        self.select_ms, self.execute_ms, self.aggregate_ms = [], [], []

    def on_round_end(self, ctx: RoundContext) -> None:
        self.metric.append(ctx.metric)
        self.train_loss.append(ctx.train_loss)
        self.selected.append(ctx.mask)
        self.select_ms.append(ctx.select_ms)
        self.execute_ms.append(ctx.execute_ms)
        self.aggregate_ms.append(ctx.aggregate_ms)


class VerboseHook(RoundHook):
    """Prints progress every ``every`` rounds, naming the eval metric.

    The numbers come from a ``repro.obs.MetricsRegistry`` — shared with a
    sibling ``TelemetryHook`` when one is attached (``capture_round`` is
    idempotent per round, so whoever fires first builds the row), its own
    otherwise — so there is exactly one source of per-round truth and no
    duplicated formatting of losses/accuracy here."""

    def __init__(self, every: int = 10,
                 registry: Optional[MetricsRegistry] = None):
        self.every = every
        self.registry = registry

    def on_run_start(self, ctx: RoundContext) -> None:
        if self.registry is None:
            shared = next((h.registry for h in ctx.engine.hooks
                           if h is not self
                           and isinstance(getattr(h, "registry", None),
                                          MetricsRegistry)), None)
            self.registry = shared or MetricsRegistry()

    def on_round_end(self, ctx: RoundContext) -> None:
        t = ctx.round_idx
        row = self.registry.capture_round(ctx)
        if t % self.every == 0 or t == ctx.fed.rounds - 1:
            eng = ctx.engine
            print(self.registry.format_round(
                row, selector=eng.selector_name, metric_name=eng.metric_name))


class AdaptiveMuHook(RoundHook):
    """Drives FedProx μ online from Lemma A.4 (``core.adaptive``).

    Retunes after each round from the cohort's observed update norms and
    rebinds the executor's μ (recompile) only on > 25 % relative moves —
    regularization must change slowly relative to selection dynamics."""

    def __init__(self, ctl: Optional[AdaptiveMu] = None, retune_threshold: float = 0.25):
        self.ctl = ctl
        self.retune_threshold = retune_threshold
        self.history: List[float] = []
        self._pending_state: Optional[Dict[str, Any]] = None

    def on_run_start(self, ctx: RoundContext) -> None:
        if self.ctl is None:
            fed = ctx.fed
            self.ctl = AdaptiveMu(local_steps=ctx.engine.spec.resolved_steps,
                                  local_lr=fed.lr, mu=fed.mu)
        if self._pending_state is not None:
            self._apply_state(self._pending_state)
            self._pending_state = None

    def on_round_end(self, ctx: RoundContext) -> None:
        new_mu = self.ctl.observe_round(
            ctx.obs_sqnorm[ctx.selected], ctx.fed.rounds - ctx.round_idx)
        self.history.append(new_mu)
        mu_now = ctx.engine.mu
        if abs(new_mu - mu_now) / max(mu_now, 1e-9) > self.retune_threshold:
            ctx.engine.set_mu(new_mu)

    def contribute(self, extras: Dict[str, Any]) -> None:
        if self.history:
            extras["mu_history"] = np.array(self.history)

    def state_dict(self) -> Optional[Dict[str, Any]]:
        out: Dict[str, Any] = {"history": [float(x) for x in self.history]}
        if self.ctl is not None:
            out.update(mu=self.ctl.mu, g_sq=self.ctl._g_sq,
                       b_sq=self.ctl._b_sq, dist_sq=self.ctl._dist_sq)
        return out

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        if self.ctl is None:
            self._pending_state = state  # applied once on_run_start builds ctl
        else:
            self._apply_state(state)

    def _apply_state(self, state: Dict[str, Any]) -> None:
        self.history = list(state.get("history", []))
        if "mu" in state:
            self.ctl.mu = state["mu"]
            self.ctl._g_sq = state["g_sq"]
            self.ctl._b_sq = state["b_sq"]
            self.ctl._dist_sq = state["dist_sq"]


class CheckpointHook(RoundHook):
    """Mid-run checkpoint/resume for federated runs (``repro.ckpt``).

    Every ``every`` rounds, round-trips the full resumable state: global
    params, ``ClientState`` (f32 or bf16 ``compact_state`` layout — bitwise,
    including the int32 ``NEVER`` sentinel), the jax PRNG key, the host
    numpy RNG state, aggregator state (momentum velocity, FedBuff buffer),
    sibling-hook state (``RoundHook.state_dict`` — e.g. the adaptive-μ
    controller's EMAs), the metric series, and whatever the running engine
    declares via its ``extra_state`` protocol — the async virtual clock with
    its pending in-flight updates and staleness counters, the hierarchical
    cloud-upload series and in-flight edge cohorts. A run killed at round t
    and resumed therefore reproduces the uninterrupted run bitwise for every
    ``round_policy × topology`` combination (tests/test_resume_matrix.py).

    Snapshots are versioned and schema-checked; a resume against the wrong
    engine kind, format version or state dtype fails loudly
    (``CheckpointMismatchError``) instead of partially restoring.
    ``resume=True`` restores the newest *readable* snapshot at run start:
    if the latest is corrupt (truncated write at the preemption instant),
    the hook warns and falls back to the next older one — but a schema or
    engine mismatch is a misconfiguration and always re-raises.
    ``keep_last=N`` garbage-collects all but the newest N snapshots after
    each save. The resumed spec must rebuild the same hook list (hook state
    is keyed by list position), with this hook *before* any
    ``KillAtRound``-style hook so the save lands ahead of the kill.

    Known limitation: top-k error-feedback residuals are not checkpointed;
    a resumed compressed run re-accumulates them from zero."""

    def __init__(self, path: str, every: int = 1, resume: bool = True,
                 keep_last: Optional[int] = None):
        if keep_last is not None and keep_last < 1:
            raise ValueError(f"keep_last must be ≥ 1, got {keep_last}")
        self.path = path
        self.every = max(every, 1)
        self.resume = resume
        self.keep_last = keep_last

    def on_run_start(self, ctx: RoundContext) -> None:
        if not self.resume:
            return
        rounds = repro_ckpt.list_federated_rounds(self.path)
        if not rounds:
            return
        errors = []
        for r in reversed(rounds):
            try:
                ctx.engine.restore(self.path, round_idx=r)
                if errors:
                    warnings.warn(
                        f"CheckpointHook: resumed from round {r} after "
                        f"skipping unreadable snapshot(s): {errors}",
                        RuntimeWarning, stacklevel=2)
                return
            except repro_ckpt.CheckpointMismatchError:
                # Wrong engine/version/schema is a misconfigured resume,
                # not disk corruption — never fall back past it.
                raise
            except Exception as e:  # truncated npz / unparseable json
                errors.append(f"round {r}: {type(e).__name__}: {e}")
        raise RuntimeError(
            f"CheckpointHook: no readable snapshot under {self.path!r} "
            f"out of {len(rounds)} candidate(s): {errors}")

    def on_round_end(self, ctx: RoundContext) -> None:
        t = ctx.round_idx
        if (t + 1) % self.every == 0 or t == ctx.fed.rounds - 1:
            tracer = ctx.engine.tracer
            tc0 = time.perf_counter() if tracer.enabled else 0.0
            ctx.engine.save(self.path)
            if self.keep_last is not None:
                repro_ckpt.prune_federated_rounds(self.path, self.keep_last)
            if tracer.enabled:
                tracer.wall_span("checkpoint_save", tc0, time.perf_counter(),
                                 track="checkpoint", cat="ckpt",
                                 args={"round": t})
                # Make the stream durable through this save: a preemption
                # landing right after it must not truncate the trace
                # mid-track (resume splices onto the last recorded event).
                tracer.flush()


class SimulatedPreemption(RuntimeError):
    """Raised by ``KillAtRound`` to simulate a mid-run kill (tests/CI)."""


class KillAtRound(RoundHook):
    """Crash-injection hook: die after round ``t`` like a preempted worker.

    ``phase="round_end"`` (default) raises from ``on_round_end`` after round
    ``t`` — list it *after* ``CheckpointHook`` so the snapshot for round
    ``t`` lands first, exactly like a preemption between rounds.
    ``phase="round_start"`` raises at the *start* of round ``t + 1``
    instead: the mid-phase variant, killing after the round-``t`` snapshot
    but once the next round's hooks have begun firing. The resume test
    matrix (tests/test_resume_matrix.py) builds on this instead of ad-hoc
    truncated-round loops."""

    PHASES = ("round_end", "round_start")

    def __init__(self, t: int, phase: str = "round_end"):
        if phase not in self.PHASES:
            raise ValueError(f"phase must be one of {self.PHASES}, got {phase!r}")
        self.t = int(t)
        self.phase = phase

    def _die(self, where: str) -> None:
        raise SimulatedPreemption(
            f"simulated preemption at {where} (KillAtRound(t={self.t}, "
            f"phase={self.phase!r}))")

    def on_round_start(self, ctx: RoundContext) -> None:
        if self.phase == "round_start" and ctx.round_idx == self.t + 1:
            self._die(f"start of round {ctx.round_idx}")

    def on_round_end(self, ctx: RoundContext) -> None:
        if self.phase == "round_end" and ctx.round_idx == self.t:
            self._die(f"end of round {ctx.round_idx}")


@register_hook("metrics")
def _make_metrics(spec: "FederatedSpec") -> MetricsHook:
    return MetricsHook()


@register_hook("verbose")
def _make_verbose(spec: "FederatedSpec") -> VerboseHook:
    return VerboseHook()


@register_hook("adaptive_mu")
def _make_adaptive_mu(spec: "FederatedSpec") -> AdaptiveMuHook:
    return AdaptiveMuHook()


@register_hook("telemetry")
def _make_telemetry(spec: "FederatedSpec") -> RoundHook:
    # Deferred import: repro.obs.hook imports this module, so resolving the
    # class at registration time would be circular. Factory bodies run at
    # hook-resolve time, long after both modules are initialized.
    from repro.obs.hook import TelemetryHook

    return TelemetryHook()


# ---------------------------------------------------------------------------
# Spec + engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FederatedSpec:
    """Declarative description of one federated run.

    ``executor`` / ``aggregator`` / ``hooks`` accept registry names
    (``EXECUTORS`` / ``AGGREGATORS`` / ``HOOKS``) or instances.
    ``executor=None`` defers to ``fed.client_execution``. ``compression``
    wraps the executor in a ``CompressedExecutor`` — incompatible
    codec/schedule pairs raise ``ExecutorCompatError`` unless the schedule
    was merely the config default, in which case the spec warns and falls
    back to sequential explicitly."""

    model: Any
    fed: FedConfig
    data: Any
    selector: Optional[str] = None
    score_cfg: Optional[HeteRoScoreConfig] = None
    sel_cfg: Optional[SelectorConfig] = None
    steps_per_round: Optional[int] = None
    eval_fn: Optional[EvalFn] = None
    metric_name: Optional[str] = None
    executor: Union[str, ClientExecutor, None] = None
    compression: Optional[str] = None    # None | 'int8' | 'topk'
    topk_frac: float = 0.1
    aggregator: Union[str, Aggregator] = "fedavg"
    hooks: Sequence[Union[str, RoundHook]] = ()
    availability: Optional[np.ndarray] = None  # (rounds, K) bool masks
    mesh: Optional[Any] = None
    mesh_axes: Optional[MeshAxes] = None
    verbose: bool = False
    # Round management: None defers to fed.round_policy ('sync' | 'async').
    # 'async' builds an AsyncFederatedEngine (fed.async_engine): event-driven
    # virtual clock, deadline-closed rounds with over-selection, buffered
    # staleness-aware aggregation. ``system`` supplies per-client round-time
    # multipliers (a fed.availability.SystemProfile or a (K,) array);
    # ``async_cfg`` the deadline/over-selection/staleness knobs.
    round_policy: Optional[str] = None
    async_cfg: Optional[Any] = None      # fed.async_engine.AsyncConfig
    system: Optional[Any] = None         # SystemProfile | (K,) multipliers
    # Federation topology: None defers to fed.topology ('flat' |
    # 'hierarchical'). 'hierarchical' builds a HierarchicalEngine
    # (fed.hierarchy): clients partitioned into FedConfig.edge_count edge
    # groups, HeteRo-Select twice per round (per-edge budgets + cross-edge
    # pooled scores), two-stage aggregation; composes with either round
    # policy. ``hier_cfg`` holds the partition/outer-budget knobs.
    topology: Optional[str] = None
    hier_cfg: Optional[Any] = None       # fed.hierarchy.HierarchyConfig
    # Keep the (K,) selection metadata in bf16 (core.state.to_bf16) — halves
    # selection-state memory at very large K. Scoring upcasts at the kernel
    # boundary, so selection differs from the f32 run only by bf16 rounding
    # of the stored observations; off by default to keep golden histories
    # bitwise.
    compact_state: bool = False

    @property
    def resolved_steps(self) -> int:
        return self.steps_per_round or self.fed.local_epochs

    @property
    def resolved_selector(self) -> str:
        return self.selector or self.fed.selector

    @property
    def resolved_round_policy(self) -> str:
        return self.round_policy or getattr(self.fed, "round_policy", "sync")

    @property
    def resolved_topology(self) -> str:
        return self.topology or getattr(self.fed, "topology", "flat")

    def build(self) -> "FederatedEngine":
        policy = self.resolved_round_policy
        if policy not in ("sync", "async"):
            raise ValueError(
                f"round_policy must be 'sync' or 'async', got {policy!r}")
        topo = self.resolved_topology
        if topo == "hierarchical":
            # The hierarchical engine owns both round policies itself (the
            # unit of cloud arrival is an edge aggregate, not a client
            # update, so flat-async cannot be stacked underneath).
            from repro.fed.hierarchy import HierarchicalEngine

            return HierarchicalEngine(self)
        if topo != "flat":
            raise ValueError(
                f"topology must be 'flat' or 'hierarchical', got {topo!r}")
        if self.hier_cfg is not None:
            raise ValueError(
                "hier_cfg is only consumed by topology='hierarchical'; "
                "the flat engines have no edge tier to apply it to")
        if getattr(self.fed, "edge_count", 0) or getattr(self.fed, "edge_budget", 0):
            # Setting edge sizing but forgetting topology='hierarchical'
            # would otherwise run a flat federation that *looks* two-tier.
            raise ValueError(
                "FedConfig.edge_count/edge_budget are only consumed by "
                "topology='hierarchical'; set FedConfig.topology (or the "
                "spec's topology field) or drop the edge fields")
        if policy == "async":
            from repro.fed.async_engine import AsyncFederatedEngine

            return AsyncFederatedEngine(self)
        if self.async_cfg is not None or self.system is not None:
            # The sync engine has no clock: silently modeling a homogeneous
            # instant fleet while the config says otherwise is how wrong
            # conclusions get drawn. Loud, like every other bad combination.
            raise ValueError(
                "async_cfg/system are only consumed by round_policy='async'; "
                "the sync engine has no wall clock to apply them to")
        return FederatedEngine(self)


def _codec_schedule_conflict(spec: FederatedSpec, name: str) -> Optional[str]:
    """Why ``spec.compression`` cannot ride the named schedule, or None."""
    if spec.compression is None or name != "batched":
        return None
    if spec.compression == "topk":
        return "compression='topk' keeps per-client host-side residuals"
    if spec.compression == "int8":
        if spec.fed.client_chunk:
            return ("compression='int8' needs the full (M, ...) client stack, "
                    "which chunked execution (client_chunk > 0) never "
                    "materializes")
        if spec.mesh is not None and spec.mesh_axes is not None \
                and spec.mesh_axes.pod is not None \
                and axis_size(spec.mesh, spec.mesh_axes.pod) > 1:
            return ("compression='int8' over a pod-sharded batched cohort "
                    "is not supported yet")
    return None


def _resolve_executor(spec: FederatedSpec) -> ClientExecutor:
    ex = spec.executor
    explicit = ex is not None
    if ex is None or isinstance(ex, str):
        name = ex or spec.fed.client_execution
        if name not in EXECUTORS:
            raise ValueError(
                f"client_execution must be one of {sorted(EXECUTORS)}, got {name!r}")
        conflict = _codec_schedule_conflict(spec, name)
        if conflict and not explicit:
            # The schedule was only the config default — downgrade loudly
            # rather than refusing a run nobody mis-configured on purpose.
            warnings.warn(
                f"{conflict}; falling back to the sequential executor (pass "
                "client_execution='sequential' to silence, or 'batched' to "
                "make this an error)", stacklevel=3)
            name = "sequential"
        ex = EXECUTORS[name](spec)
    if spec.compression is not None:
        # Explicitly-requested incompatible pairs fail in here, loudly.
        ex = CompressedExecutor(ex, spec.compression, spec.topk_frac)
    return ex


def _resolve_aggregator(spec: FederatedSpec) -> Aggregator:
    agg = spec.aggregator
    if isinstance(agg, str):
        if agg not in AGGREGATORS:
            raise ValueError(
                f"aggregator must be one of {sorted(AGGREGATORS)}, got {agg!r}")
        agg = AGGREGATORS[agg](spec)
    return agg


def _resolve_hooks(spec: FederatedSpec) -> List[RoundHook]:
    hooks: List[RoundHook] = []
    for h in spec.hooks:
        if isinstance(h, str):
            if h not in HOOKS:
                raise ValueError(f"unknown hook {h!r}; registered: {sorted(HOOKS)}")
            h = HOOKS[h](spec)
        hooks.append(h)
    if spec.verbose and not any(isinstance(h, VerboseHook) for h in hooks):
        hooks.append(VerboseHook())
    # Stable priority order (higher first): telemetry producers run before
    # the hooks that read their registry, wherever the spec listed them.
    hooks.sort(key=lambda h: -getattr(h, "priority", 0))
    # The metrics hook always runs first so every other hook (checkpointing
    # in particular) sees the round's series already appended.
    mh = next((h for h in hooks if isinstance(h, MetricsHook)), None)
    if mh is None:
        mh = MetricsHook()
    else:
        hooks.remove(mh)
    hooks.insert(0, mh)
    return hooks


class FederatedEngine:
    """Algorithm-1 skeleton over pluggable executor / aggregator / hooks.

    One ``run()`` = ``fed.rounds`` rounds of: split key → select cohort →
    ``executor.run_round`` → ``aggregator.reduce`` → fold observations into
    ``ClientState`` → eval → hooks. The engine owns only the skeleton and
    the resumable state (params, client state, RNGs, byte counters); every
    behaviour beyond that is a plugin."""

    def __init__(self, spec: FederatedSpec):
        self.spec = spec
        self.executor = _resolve_executor(spec)
        self.aggregator = _resolve_aggregator(spec)
        self.hooks = _resolve_hooks(spec)
        self.metrics = next(h for h in self.hooks if isinstance(h, MetricsHook))

        self.selector_name = spec.resolved_selector
        score_cfg = spec.score_cfg or HeteRoScoreConfig()
        sel_cfg = spec.sel_cfg or SelectorConfig(num_selected=spec.fed.num_selected)
        select = make_selector(self.selector_name, sel_cfg, score_cfg)
        if spec.availability is not None:
            select = fed_avail.mask_selector(
                select, jnp.asarray(spec.availability),
                num_selected=spec.fed.num_selected)
        self._select = jax.jit(select)

        self.eval_fn = spec.eval_fn or default_eval
        self.metric_name = spec.metric_name or (
            "metric" if spec.eval_fn is not None else default_metric_name(spec.model))

        # Resumable run state (populated by run() / restore()).
        self.mu = spec.fed.mu
        # The telemetry plane (repro.obs). TelemetryHook swaps in a live
        # TraceRecorder at run start; the default no-op recorder makes
        # every instrumentation site one attribute read when disabled.
        self.tracer = NULL_TRACER
        # Async subclasses replace this with a live VirtualClock in run();
        # hooks discover it via ``getattr(engine, "clock", None)`` so the
        # serving tier shares one event queue with async training and falls
        # back to its own clock under the sync policy.
        self.clock = None
        self.params: Any = None
        self.state: Optional[ClientState] = None
        self.key: Optional[jax.Array] = None
        self.rng: Optional[np.random.Generator] = None
        self.start_round = 0
        self.wire_total = 0
        self.raw_total = 0
        self._rounds_done = 0

    # -- lifecycle ---------------------------------------------------------

    def set_mu(self, mu: float) -> None:
        """Rebind the FedProx coefficient (executor recompiles — rare)."""
        self.mu = float(mu)
        self.executor.set_mu(self.mu)

    def run(self) -> FLResult:
        spec, fed = self.spec, self.spec.fed
        self.key = jax.random.PRNGKey(fed.seed)
        self.params = spec.model.init_params(jax.random.PRNGKey(fed.seed + 1))
        self.state = init_client_state(
            spec.data.num_clients, jnp.asarray(spec.data.label_js, jnp.float32))
        if spec.compact_state:
            self.state = to_bf16(self.state)
        self.rng = np.random.default_rng(fed.seed)
        self.start_round = 0
        self._rounds_done = 0
        self.metrics.reset()  # before hooks — a resume hook repopulates these

        ctx = RoundContext(engine=self)
        for h in self.hooks:
            h.on_run_start(ctx)

        eval_batch = spec.data.eval_batch()
        for t in range(self.start_round, fed.rounds):
            ctx.round_idx = t
            with round_step(t):
                for h in self.hooks:
                    h.on_round_start(ctx)
                self._run_round(ctx, t, eval_batch)
                for h in self.hooks:
                    h.on_round_end(ctx)

        extras: Dict[str, Any] = {}
        for h in self.hooks:
            h.on_run_end(ctx)
            h.contribute(extras)
        return self._result(extras)

    def _run_round(self, ctx: RoundContext, t: int, eval_batch: Any) -> None:
        spec, tr = self.spec, self.tracer
        with phase("select", ctx, tr, args={"round": t}):
            self.key, sk = jax.random.split(self.key)
            mask, _ = self._select(sk, self.state, jnp.int32(t))
            mask_np = np.asarray(mask)  # device sync — the selection phase ends
            selected = np.flatnonzero(mask_np)

        with phase("execute", ctx, tr,
                   args={"round": t, "cohort": int(len(selected))}):
            weights = self.aggregator.cohort_weights(selected, spec.data)
            cohort = self.executor.run_round(self.params, selected, self.rng,
                                             weights=weights)
        with phase("aggregate", ctx, tr, args={"round": t}):
            self.params = self.aggregator.reduce(self.params, cohort)
            self.wire_total += cohort.wire_bytes
            self.raw_total += cohort.raw_bytes

        with phase("state_update", recorder=tr, args={"round": t}):
            obs_loss, obs_sqnorm = self._dense_observations(selected, cohort)
            self.state = update_client_state(
                self.state,
                round_idx=jnp.int32(t),
                selected_mask=jnp.asarray(mask_np),
                observed_loss=jnp.asarray(obs_loss),
                observed_sqnorm=jnp.asarray(obs_sqnorm),
            )

        ctx.mask = mask_np
        ctx.selected = selected
        ctx.obs_loss = obs_loss
        ctx.obs_sqnorm = obs_sqnorm
        with phase("eval", recorder=tr, args={"round": t}):
            ctx.metric = self.eval_fn(spec.model, self.params, eval_batch)
        ctx.train_loss = float(np.mean(obs_loss[selected])) if len(selected) else 0.0
        ctx.sim_time = float(t + 1)  # sync rounds cost "1" on the time axis
        self._rounds_done = t + 1

    def _dense_observations(self, selected: np.ndarray,
                            cohort: CohortUpdates) -> Tuple[np.ndarray, np.ndarray]:
        k = self.spec.data.num_clients
        if isinstance(cohort.mean_loss, np.ndarray):
            obs_loss = np.zeros(k, np.float32)
            obs_sqnorm = np.zeros(k, np.float32)
            obs_loss[selected] = cohort.mean_loss
            obs_sqnorm[selected] = cohort.update_sqnorm
            return obs_loss, obs_sqnorm
        loss_j, sq_j = scatter_observations(
            k, jnp.asarray(selected), cohort.mean_loss, cohort.update_sqnorm)
        return np.asarray(loss_j), np.asarray(sq_j)

    def _result(self, extras: Dict[str, Any]) -> FLResult:
        sel_hist = np.stack(self.metrics.selected)
        return FLResult(
            accuracy=np.array(self.metrics.metric),
            train_loss=np.array(self.metrics.train_loss),
            selection_counts=sel_hist.sum(axis=0),
            selected_history=sel_hist,
            params=self.params,
            wire_bytes=self.wire_total,
            raw_bytes=self.raw_total,
            mu_history=extras.get("mu_history"),
            metric_name=self.metric_name,
            wall_clock=extras.get("wall_clock"),
            round_staleness=extras.get("round_staleness"),
            cloud_uploads=extras.get("cloud_uploads"),
            select_ms=np.asarray(self.metrics.select_ms),
            execute_ms=np.asarray(self.metrics.execute_ms),
            aggregate_ms=np.asarray(self.metrics.aggregate_ms),
            serving=extras.get("serving"),
            served_metric=extras.get("served_metric"),
            telemetry=extras.get("telemetry"),
        )

    # -- checkpoint / resume ----------------------------------------------
    #
    # The base engine owns the snapshot layout (versioned + schema-checked,
    # see repro.ckpt); subclasses contribute their per-round extras through
    # the four-method ``extra_state`` protocol below instead of
    # reimplementing save/restore. The snapshot records ``snapshot_kind`` so
    # a resume against the wrong engine fails loudly before any leaf loads.

    @property
    def snapshot_kind(self) -> str:
        """Engine identity stamped into (and verified against) snapshots."""
        return "sync/flat"

    def extra_state(self) -> Tuple[Dict[str, Any], Dict[str, np.ndarray],
                                   Dict[str, Any]]:
        """Subclass hook: extra ``(trees, arrays, meta)`` to persist.

        Tree/array names share one namespace with the base snapshot
        (``params``, ``client_state``, ``rng_key``, ``aggregator_state``;
        ``metric``, ``train_loss``, ``selected_history``) — pick new ones.
        The meta dict is stored under the snapshot's ``"extra"`` key and
        handed back verbatim to ``extra_likes`` / ``load_extra_state``."""
        return {}, {}, {}

    def extra_likes(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        """Subclass hook: restore templates for ``extra_state`` trees.

        Receives the snapshot's full meta (``meta["extra"]`` included)
        *before* arrays load — the template set may depend on it (e.g. one
        delta tree per in-flight completion, keyed by event seq)."""
        return {}

    def load_extra_state(self, trees: Dict[str, Any],
                         arrays: Dict[str, np.ndarray],
                         meta: Dict[str, Any]) -> None:
        """Subclass hook: re-install restored extras into engine fields."""

    def save(self, path: str) -> str:
        """Write the full resumable state after the current round."""
        t = self._rounds_done
        trees = {"params": self.params, "client_state": self.state,
                 "rng_key": self.key}
        agg_state = self.aggregator.get_state()
        if agg_state is not None:
            trees["aggregator_state"] = agg_state
        arrays = {
            "metric": np.asarray(self.metrics.metric, np.float64),
            "train_loss": np.asarray(self.metrics.train_loss, np.float64),
            "selected_history": np.stack(self.metrics.selected).astype(np.uint8),
            # Phase wall times (format v3): resumed runs report the real
            # prefix timings instead of a zero backfill.
            "select_ms": np.asarray(self.metrics.select_ms, np.float64),
            "execute_ms": np.asarray(self.metrics.execute_ms, np.float64),
            "aggregate_ms": np.asarray(self.metrics.aggregate_ms, np.float64),
        }
        extra_trees, extra_arrays, extra_meta = self.extra_state()
        clash = (set(trees) | {"aggregator_state"}) & set(extra_trees)
        clash |= set(arrays) & set(extra_arrays)
        if clash:
            raise ValueError(f"extra_state name collision: {sorted(clash)}")
        trees.update(extra_trees)
        arrays.update(extra_arrays)
        hook_states = {str(i): s for i, h in enumerate(self.hooks)
                       if (s := h.state_dict()) is not None}
        meta = {
            "round": t,
            "engine": self.snapshot_kind,
            "mu": self.mu,
            "wire_bytes": self.wire_total,
            "raw_bytes": self.raw_total,
            "metric_name": self.metric_name,
            "np_rng_state": self.rng.bit_generator.state,
            "hook_states": hook_states,
            "extra": extra_meta,
        }
        return repro_ckpt.save_federated_round(
            path, round_idx=t, trees=trees, arrays=arrays, meta=meta)

    def restore(self, path: str, round_idx: Optional[int] = None) -> int:
        """Restore a ``save()`` snapshot; returns the round to resume from.

        Must be called after ``run()`` initialized params/state/key (the
        restore is structure-driven) — ``CheckpointHook`` does this from
        ``on_run_start``. Verifies the snapshot was written by the same
        engine kind before anything loads; all schema/dtype checks raise
        ``repro.ckpt.CheckpointMismatchError`` rather than partially
        restoring."""
        head = repro_ckpt.read_federated_meta(path, round_idx)
        written_by = head.get("engine")
        if written_by != self.snapshot_kind:
            raise repro_ckpt.CheckpointMismatchError(
                f"snapshot round {head['round']} under {path!r} was written "
                f"by engine {written_by!r}; this engine is "
                f"{self.snapshot_kind!r} — resume with a matching "
                "round_policy/topology configuration")
        agg_like = self.aggregator.get_state()
        if agg_like is None:
            # Momentum velocity shares the params structure but is always
            # f32 (ServerMomentum accumulates delta in f32) — the template
            # must not truncate it to bf16 param dtypes.
            agg_like = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), self.params)
        likes = {"params": self.params, "client_state": self.state,
                 "rng_key": self.key, "aggregator_state": agg_like}
        likes.update(self.extra_likes(head))
        trees, arrays, meta = repro_ckpt.restore_federated_round(
            path, likes=likes, round_idx=int(head["round"]),
            optional=("aggregator_state",))
        self.params = trees["params"]
        self.state = trees["client_state"]
        self.key = trees["rng_key"]
        if "aggregator_state" in trees:
            self.aggregator.set_state(trees["aggregator_state"])
        self.rng.bit_generator.state = meta["np_rng_state"]
        self.wire_total = int(meta.get("wire_bytes", 0))
        self.raw_total = int(meta.get("raw_bytes", 0))
        if abs(meta.get("mu", self.mu) - self.mu) > 1e-12:
            self.set_mu(meta["mu"])
        self.metrics.metric = [float(x) for x in arrays["metric"]]
        self.metrics.train_loss = [float(x) for x in arrays["train_loss"]]
        self.metrics.selected = [m.astype(bool)
                                 for m in arrays["selected_history"]]
        self.metrics.select_ms = [float(x) for x in arrays["select_ms"]]
        self.metrics.execute_ms = [float(x) for x in arrays["execute_ms"]]
        self.metrics.aggregate_ms = [float(x) for x in arrays["aggregate_ms"]]
        for i_str, s in meta.get("hook_states", {}).items():
            i = int(i_str)
            if i < len(self.hooks):
                self.hooks[i].load_state_dict(s)
        self.load_extra_state(trees, arrays, meta)
        self.start_round = int(meta["round"])
        self._rounds_done = self.start_round
        return self.start_round
