"""Event-driven asynchronous federation: deadlines, buffers, staleness.

The synchronous engine (``fed.engine``) blocks every round on the slowest
selected client, so system heterogeneity never costs wall-clock time and
the paper's staleness machinery has nothing real to measure. This module is
the asynchronous execution regime on top of the same plugin surface:

  * ``AsyncFederatedEngine`` — ``FederatedEngine`` with the round loop
    re-timed by a virtual wall clock (``fed.clock``). Each round t:

      1. **Dispatch** — select ``⌈m·(1+ε)⌉`` clients (Oort-style
         over-selection) using the HeteRo-Select score whose freshness term
         (Eq 7) consumes *clock-measured* staleness — elapsed virtual time
         since each client's update was last aggregated, in units of the
         reference round duration — via ``core.selection.make_async_selector``.
         Clients still in flight from earlier rounds are skipped (a real
         server does not re-dispatch a busy device).
      2. **Train** — the whole dispatch cohort trains in ONE call of the
         regular executor (the batched vmap path stays the compute
         substrate); completions are *simulated events*: each client's
         finished update is held back and scheduled on the clock at
         ``now + latency_k`` (``SystemProfile`` multipliers × base × jitter).
      3. **Close** — the round closes at ``now + deadline``. Updates due by
         then — including stragglers dispatched in *earlier* rounds —
         aggregate now; later ones stay pending and carry forward as stale
         arrivals. If nothing arrived, the deadline extends to the next
         completion (a real federation waits rather than ship nothing).
      4. **Aggregate** — ``BufferedAggregator`` (FedBuff-style) applies the
         arrivals as parameter deltas against the global version each client
         trained on, down-weighted polynomially in staleness:
         w_i ∝ (1+τ_i)^(−a).

  * ``BufferedAggregator`` — implements the PR-3 ``Aggregator`` protocol
    (registered as ``"fedbuff"``), so it also composes with the synchronous
    engine, where every update has τ = 0 and it degenerates to FedAvg.

Equivalence contract: with equal latencies, ``deadline=∞`` and ``ε = 0``
the async engine replays the synchronous run — same selector draws (the
clock-staleness equals the round counter exactly), same executor calls,
FedAvg-equivalent aggregation up to float reassociation — pinned by
tests/test_async_engine.py.

References: FedBuff (Nguyen et al., AISTATS 2022) for buffered aggregation
and polynomial staleness discounting; Oort (Lai et al., OSDI 2021) for
over-selection and deadline-based round management; the client-selection
survey (Fu et al., 2022) and FilFL (Fourati et al., 2023) for the
sync-to-deployable gap this closes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.scoring import HeteRoScoreConfig
from repro.core.selection import SelectorConfig, make_async_selector
from repro.core.state import update_client_state
from repro.fed import availability as fed_avail
from repro.fed import server as fed_server
from repro.fed.clock import Completion, LatencyModel, VirtualClock
from repro.fed.engine import (
    Aggregator,
    BatchedExecutor,
    CohortUpdates,
    ExecutorCompatError,
    FedAvg,
    FederatedEngine,
    FederatedSpec,
    FLResult,
    RoundContext,
    register_aggregator,
)
from repro.obs.phases import phase

# Staleness reported for never-contacted clients (clipped by Eq 7's T_max).
NEVER_STALE = 1.0e6


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Knobs of the asynchronous round manager.

    deadline:          virtual-time budget per round; arrivals after
                       ``dispatch + deadline`` carry forward as stale
                       updates. ``inf`` waits for the full dispatch cohort
                       (no stragglers ever carry over).
    over_select_frac:  ε — dispatch ``⌈m·(1+ε)⌉`` clients so the deadline
                       still harvests ~m updates when stragglers miss it.
    staleness_power:   a in the FedBuff discount w(τ) = (1+τ)^(−a).
    server_lr:         η_s scaling the aggregated delta step.
    min_updates:       extend past the deadline until at least this many
                       updates arrived (never aggregate an empty round).
    max_staleness:     drop updates staler than this many model versions
                       (None keeps everything, the FedBuff default).
    base_latency:      virtual-time cost of one unit-speed client round —
                       the unit the deadline is expressed in.
    jitter:            per-dispatch log-normal latency noise (sigma); > 0
                       consumes the engine's host RNG stream.
    """

    deadline: float = math.inf
    over_select_frac: float = 0.0
    staleness_power: float = 0.5
    server_lr: float = 1.0
    min_updates: int = 1
    max_staleness: Optional[int] = None
    base_latency: float = 1.0
    jitter: float = 0.0

    def __post_init__(self):
        if self.deadline <= 0:
            raise ValueError("deadline must be > 0 (use math.inf for no deadline)")
        if self.over_select_frac < 0:
            raise ValueError("over_select_frac must be ≥ 0")
        if self.base_latency <= 0:
            raise ValueError("base_latency must be > 0")


def staleness_weights(staleness: np.ndarray, power: float) -> np.ndarray:
    """FedBuff's polynomial discount w_i = (1+τ_i)^(−power), unnormalized."""
    tau = np.maximum(np.asarray(staleness, np.float64), 0.0)
    return (1.0 + tau) ** (-float(power))


def drain_due_arrivals(clock: "VirtualClock", acfg: "AsyncConfig", t: int,
                       dispatch_time: float,
                       in_flight: np.ndarray) -> tuple:
    """Close one round on the clock and collect its aggregatable arrivals.

    The deadline-close semantics shared by the flat async engine (arrivals
    are client updates) and the hierarchical one (arrivals are edge
    aggregates — ``fed.hierarchy``; ``in_flight`` is indexed by whatever
    ``Completion.client`` holds):

      * the round closes at ``dispatch_time + acfg.deadline``; with an
        infinite deadline it waits for everything currently in flight;
      * every popped arrival frees its in-flight slot, then the staleness
        filter applies: arrivals older than ``acfg.max_staleness`` model
        versions are dropped (counted, never silent);
      * ``min_updates`` counts *aggregatable* arrivals — events the
        staleness filter discarded must not satisfy the never-an-empty-round
        promise — so the close extends completion-by-completion until
        enough arrive or nothing is pending.

    Returns ``(kept, dropped)``: the arrivals to aggregate, in (time, seq)
    order, and how many the staleness filter discarded.
    """
    if math.isinf(acfg.deadline):
        close = clock.latest_time()
        close = dispatch_time if close is None else close
    else:
        close = dispatch_time + acfg.deadline
    kept: List[Completion] = []
    dropped = 0

    def ingest(events: List[Completion]) -> None:
        nonlocal dropped
        for ev in events:
            in_flight[ev.client] = False
            if (acfg.max_staleness is not None
                    and t - ev.dispatch_round > acfg.max_staleness):
                dropped += 1
            else:
                kept.append(ev)

    ingest(clock.pop_due(close))
    while len(kept) < acfg.min_updates and len(clock):
        ingest(clock.pop_due(clock.peek_time()))
    return kept, dropped


def upgrade_async_aggregator(agg: Aggregator, acfg: "AsyncConfig") -> Aggregator:
    """The async-mode aggregator contract, shared with ``fed.hierarchy``.

    The config-default ``FedAvg`` silently becomes a ``BufferedAggregator``
    (async's FedAvg *is* fedbuff — every update has τ = 0 under equal
    latencies); anything else must declare ``supports_deltas`` because
    async arrivals are deltas against different global versions and cannot
    be plainly averaged.
    """
    if type(agg) is FedAvg:
        return BufferedAggregator(staleness_power=acfg.staleness_power,
                                  server_lr=acfg.server_lr)
    if not getattr(agg, "supports_deltas", False):
        raise ValueError(
            f"aggregator {getattr(agg, 'name', agg)!r} cannot aggregate "
            "async delta cohorts (updates arrive as deltas against "
            "different global versions); use 'fedbuff' or an Aggregator "
            "with supports_deltas=True")
    return agg


@dataclasses.dataclass
class PendingUpdate:
    """What a completion event carries back to the server."""

    delta: Any          # f32 pytree: w_client − w_global(dispatch round)
    loss: float
    sqnorm: float
    weight: float = 1.0  # data-size weight captured at dispatch


class BufferedAggregator(Aggregator):
    """FedBuff-style buffered aggregation with polynomial staleness discount.

    ``reduce`` consumes delta-form cohorts (``CohortUpdates.delta_list`` +
    ``staleness``): each arrival is a parameter delta against the global
    version its client trained on, weighted w_i ∝ (1+τ_i)^(−a) — times the
    data-size weight when the spec's ``cohort_weights`` provided one — and
    applied as one fused step (``fed.server.apply_weighted_deltas``).

    Under the synchronous engine (param-form cohorts) every update has
    τ = 0, so this degenerates to FedAvg scaled by ``server_lr`` — which is
    what lets ``aggregator="fedbuff"`` be a drop-in in either mode.
    """

    name = "fedbuff"
    supports_deltas = True

    def __init__(self, staleness_power: float = 0.5, server_lr: float = 1.0):
        self.staleness_power = float(staleness_power)
        self.server_lr = float(server_lr)

    def reduce(self, global_params, cohort: CohortUpdates):
        if cohort.delta_list is not None:
            n = len(cohort.delta_list)
            tau = (np.zeros(n) if cohort.staleness is None
                   else np.asarray(cohort.staleness, np.float64))
            w = staleness_weights(tau, self.staleness_power)
            if cohort.weights is not None:
                w = w * np.asarray(cohort.weights, np.float64)
            return fed_server.apply_weighted_deltas(
                global_params, cohort.delta_list, jnp.asarray(w, jnp.float32),
                server_lr=self.server_lr)
        # Sync-engine cohort: same-anchor params — one zero-staleness delta.
        delta = fed_server.params_delta_f32(self._mean(cohort), global_params)
        return fed_server.apply_weighted_deltas(
            global_params, [delta], jnp.ones((1,), jnp.float32),
            server_lr=self.server_lr)


@register_aggregator("fedbuff")
def _make_fedbuff(spec: FederatedSpec) -> BufferedAggregator:
    acfg = spec.async_cfg or AsyncConfig()
    return BufferedAggregator(staleness_power=acfg.staleness_power,
                              server_lr=acfg.server_lr)


def _resolve_multipliers(system: Any, num_clients: int) -> np.ndarray:
    """(K,) per-client round-time multipliers from whatever the spec gave."""
    if system is None:
        return np.ones(num_clients)
    speeds = getattr(system, "speeds", None)
    mult = np.asarray(speeds() if callable(speeds) else system, np.float64)
    if mult.shape != (num_clients,):
        raise ValueError(
            f"system profile must yield ({num_clients},) multipliers, "
            f"got shape {mult.shape}")
    return mult


class AsyncFederatedEngine(FederatedEngine):
    """Deadline-managed asynchronous rounds over the plugin surface.

    Built by ``FederatedSpec.build()`` when the resolved round policy is
    ``'async'`` (``FedConfig.round_policy`` or the spec field). Differences
    from the synchronous skeleton are confined to *when* updates reach the
    server; scoring, executors, hooks and metrics all reuse the sync
    machinery — including ``CheckpointHook``: the virtual clock, pending
    in-flight updates and staleness counters checkpoint via the engine's
    ``extra_state`` protocol, so a killed async run resumes bitwise.
    """

    def __init__(self, spec: FederatedSpec):
        super().__init__(spec)
        fed = spec.fed
        self.acfg: AsyncConfig = spec.async_cfg or AsyncConfig()
        k = spec.data.num_clients
        mult = _resolve_multipliers(spec.system, k)
        self.latency = LatencyModel(mult, base=self.acfg.base_latency,
                                    jitter=self.acfg.jitter)
        self.m_over = min(
            k, int(math.ceil(fed.num_selected * (1.0 + self.acfg.over_select_frac))))

        score_cfg = spec.score_cfg or HeteRoScoreConfig()
        sel_cfg = spec.sel_cfg or SelectorConfig(num_selected=fed.num_selected)
        sel_cfg = dataclasses.replace(sel_cfg, num_selected=self.m_over)
        # Oort's system-utility term: preferred/actual round duration.
        speeds = jnp.asarray(
            self.latency.reference_time()
            / (self.latency.base * self.latency.multipliers), jnp.float32)
        select = make_async_selector(self.selector_name, sel_cfg, score_cfg,
                                     speeds=speeds)
        if spec.availability is not None:
            select = fed_avail.mask_async_selector(
                select, jnp.asarray(spec.availability),
                num_selected=self.m_over)
        self._select_async = jax.jit(select)

        self._require_per_client_updates()
        self._upgrade_aggregator()

    # -- construction checks ----------------------------------------------

    def _require_per_client_updates(self) -> None:
        """Async needs each client's update separately (deltas, held back)."""
        inner = getattr(self.executor, "inner", self.executor)
        if getattr(inner, "kind", None) == "batched":
            if self.spec.fed.client_chunk:
                raise ExecutorCompatError(
                    "async rounds need every client's update separately, but "
                    "chunked batched execution (FedConfig.client_chunk > 0) "
                    "never materializes the (M, ...) client stack; set "
                    "client_chunk=0 or use the sequential executor")
            if isinstance(inner, BatchedExecutor):
                inner.keep_client_params = True

    def _upgrade_aggregator(self) -> None:
        self.aggregator = upgrade_async_aggregator(self.aggregator, self.acfg)

    # -- lifecycle ---------------------------------------------------------

    def run(self) -> FLResult:
        k = self.spec.data.num_clients
        # Created before super().run() fires on_run_start, so hooks that
        # interleave with training time (repro.serve.PublishHook) can attach
        # their channel sub-queues to this clock from the first round. A
        # checkpoint resume replaces the clock (load_extra_state); channels
        # are drained-per-round ephemera, so nothing is lost by the swap.
        self.clock = VirtualClock()
        self._in_flight = np.zeros(k, bool)
        # Virtual dispatch time of the round in which each client's update
        # was last aggregated: staleness = (now − this) / reference round
        # duration = model versions since last contribution.
        self._last_contact = np.full(k, -np.inf)
        self._dur_sum = 0.0
        self._dur_n = 0
        self.wall_clock: List[float] = []
        self.round_staleness: List[float] = []
        self.stragglers_carried = 0
        self.updates_dropped = 0
        return super().run()

    def _ref_time(self) -> float:
        """Reference round duration: realized mean, else the latency median."""
        if self._dur_n:
            return self._dur_sum / self._dur_n
        return self.latency.reference_time()

    def _staleness_override(self) -> jax.Array:
        gap = self.clock.now - self._last_contact
        out = np.where(np.isfinite(gap), gap / self._ref_time(), NEVER_STALE)
        return jnp.asarray(out, jnp.float32)

    # -- the async round ---------------------------------------------------

    def _run_round(self, ctx: RoundContext, t: int, eval_batch: Any) -> None:
        spec, acfg = self.spec, self.acfg
        dispatch_time = self.clock.now

        # 1. Dispatch: over-select on clock-measured staleness, skip busy.
        tr = self.tracer
        with phase("select", ctx, tr, args={"round": t}):
            self.key, sk = jax.random.split(self.key)
            mask, _ = self._select_async(sk, self.state, jnp.int32(t),
                                         self._staleness_override())
            mask_np = np.asarray(mask) & ~self._in_flight
            selected = np.flatnonzero(mask_np)

        # 2. Train the dispatch cohort in one executor call; hold the
        #    updates back and schedule their completions on the clock.
        with phase("execute", ctx, tr,
                   args={"round": t, "dispatched": int(len(selected))}):
            if len(selected):
                weights = self.aggregator.cohort_weights(selected, spec.data)
                w_np = (np.ones(len(selected)) if weights is None
                        else np.asarray(weights, np.float64))
                cohort = self.executor.run_round(self.params, selected, self.rng,
                                                 weights=None)
                self.wire_total += cohort.wire_bytes
                self.raw_total += cohort.raw_bytes
                lat = self.latency.sample(selected, self.rng)
                losses = np.asarray(cohort.mean_loss, np.float32)
                sqnorms = np.asarray(cohort.update_sqnorm, np.float32)
                for i, c in enumerate(selected):
                    payload = PendingUpdate(
                        delta=self._client_delta(cohort, i),
                        loss=float(losses[i]), sqnorm=float(sqnorms[i]),
                        weight=float(w_np[i]))
                    self.clock.schedule(lat[i], c, t, payload)
                self._in_flight[selected] = True

        # 3. Close the round at the deadline; carry late updates forward.
        # 4. Buffered aggregation of the arrivals.
        with phase("aggregate", ctx, tr, args={"round": t}) as agg:
            kept, dropped = drain_due_arrivals(self.clock, acfg, t, dispatch_time,
                                               self._in_flight)
            self.updates_dropped += dropped
            stale = np.asarray([t - ev.dispatch_round for ev in kept], np.float32)
            n_stragglers = sum(1 for ev in kept if ev.dispatch_round < t)
            agg.args.update(arrivals=int(len(kept)), stragglers=n_stragglers)
            if kept:
                agg_cohort = CohortUpdates(
                    mean_loss=np.asarray([ev.payload.loss for ev in kept], np.float32),
                    update_sqnorm=np.asarray([ev.payload.sqnorm for ev in kept],
                                             np.float32),
                    delta_list=[ev.payload.delta for ev in kept],
                    staleness=stale,
                    weights=np.asarray([ev.payload.weight for ev in kept],
                                       np.float32),
                )
                self.params = self.aggregator.reduce(self.params, agg_cohort)

        # 5. Metadata fold for the arrivals.
        with phase("state_update", recorder=tr, args={"round": t}):
            obs_loss = np.zeros(spec.data.num_clients, np.float32)
            obs_sqnorm = np.zeros(spec.data.num_clients, np.float32)
            if kept:
                arr_ids = np.asarray([ev.client for ev in kept], np.int64)
                arr_mask = np.zeros(spec.data.num_clients, bool)
                arr_mask[arr_ids] = True
                obs_loss[arr_ids] = agg_cohort.mean_loss
                obs_sqnorm[arr_ids] = agg_cohort.update_sqnorm
                self.state = update_client_state(
                    self.state,
                    round_idx=jnp.int32(t),
                    selected_mask=jnp.asarray(arr_mask),
                    observed_loss=jnp.asarray(obs_loss),
                    observed_sqnorm=jnp.asarray(obs_sqnorm),
                )
                self._last_contact[arr_ids] = dispatch_time

        # 6. Clock bookkeeping + the usual round tail.
        duration = self.clock.now - dispatch_time
        self._dur_sum += duration
        self._dur_n += 1
        self.stragglers_carried += n_stragglers
        self.wall_clock.append(self.clock.now)
        self.round_staleness.append(float(stale.mean()) if len(stale) else 0.0)

        ctx.mask = mask_np
        ctx.selected = selected
        ctx.obs_loss = obs_loss
        ctx.obs_sqnorm = obs_sqnorm
        ctx.sim_time = self.clock.now
        ctx.num_arrivals = len(kept)
        ctx.num_stragglers = n_stragglers
        with phase("eval", recorder=tr, args={"round": t}):
            ctx.metric = self.eval_fn(spec.model, self.params, eval_batch)
        ctx.train_loss = (float(np.mean([ev.payload.loss for ev in kept]))
                          if kept else 0.0)
        self._rounds_done = t + 1

    def _client_delta(self, cohort: CohortUpdates, i: int) -> Any:
        """f32 delta of cohort member i against the current global anchor."""
        if cohort.param_list is not None:
            w_i = cohort.param_list[i]
        elif cohort.stacked_params is not None:
            w_i = jax.tree_util.tree_map(lambda x: x[i], cohort.stacked_params)
        else:
            raise ExecutorCompatError(
                "async rounds need per-client updates, but the executor "
                "returned only the fused cohort mean")
        return fed_server.params_delta_f32(w_i, self.params)

    def _result(self, extras) -> FLResult:
        extras.setdefault("wall_clock", np.asarray(self.wall_clock))
        extras.setdefault("round_staleness", np.asarray(self.round_staleness))
        return super()._result(extras)

    # -- checkpoint / resume ----------------------------------------------
    #
    # The base engine owns the snapshot (params, ClientState, RNG streams,
    # aggregator state, metric series); the async regime contributes its
    # time axis through the extra_state protocol: the virtual clock with
    # every pending in-flight completion (each a PendingUpdate whose delta
    # pytree is persisted as its own schema-checked tree keyed by the
    # event's seq), the in-flight / last-contact vectors the staleness
    # override reads, the realized-duration stats behind _ref_time, and the
    # wall_clock / round_staleness series. A run killed at round t resumes
    # bitwise — same selector draws, same arrival order, same wall-clock
    # trace (tests/test_resume_matrix.py).

    @property
    def snapshot_kind(self) -> str:
        return "async/flat"

    def extra_state(self):
        trees = {}
        pending_meta = {}
        for ev in self.clock.pending():
            trees[f"pending/{ev.seq}"] = ev.payload.delta
            pending_meta[str(ev.seq)] = {
                "loss": ev.payload.loss, "sqnorm": ev.payload.sqnorm,
                "weight": ev.payload.weight,
            }
        arrays = {
            "in_flight": self._in_flight,
            # Holds -inf for never-contacted clients: must travel as an
            # array shard, not JSON (which cannot encode infinities).
            "last_contact": np.asarray(self._last_contact, np.float64),
            "wall_clock": np.asarray(self.wall_clock, np.float64),
            "round_staleness": np.asarray(self.round_staleness, np.float64),
        }
        meta = {
            "clock": self.clock.state_dict(),
            "pending": pending_meta,
            "dur_sum": self._dur_sum,
            "dur_n": self._dur_n,
            "stragglers_carried": self.stragglers_carried,
            "updates_dropped": self.updates_dropped,
        }
        return trees, arrays, meta

    def extra_likes(self, meta):
        # Pending deltas share the params structure but are always f32
        # (params_delta_f32), whatever dtype the model params use.
        delta_like = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), self.params)
        return {f"pending/{ev['seq']}": delta_like
                for ev in meta["extra"]["clock"]["events"]}

    def load_extra_state(self, trees, arrays, meta):
        extra = meta["extra"]
        payloads = {
            int(seq): PendingUpdate(
                delta=trees[f"pending/{seq}"], loss=info["loss"],
                sqnorm=info["sqnorm"], weight=info["weight"])
            for seq, info in extra["pending"].items()
        }
        self.clock = VirtualClock()
        self.clock.load_state_dict(extra["clock"], payloads)
        self._in_flight = np.asarray(arrays["in_flight"], bool).copy()
        self._last_contact = np.asarray(arrays["last_contact"],
                                        np.float64).copy()
        self._dur_sum = float(extra["dur_sum"])
        self._dur_n = int(extra["dur_n"])
        self.stragglers_carried = int(extra["stragglers_carried"])
        self.updates_dropped = int(extra["updates_dropped"])
        self.wall_clock = [float(x) for x in arrays["wall_clock"]]
        self.round_staleness = [float(x) for x in arrays["round_staleness"]]
