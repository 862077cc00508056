"""Event-driven asynchronous federation: deadlines, buffers, staleness.

The synchronous policy blocks every round on the slowest selected client,
so system heterogeneity never costs wall-clock time and the paper's
staleness machinery has nothing real to measure. This module is the
asynchronous round policy of the one round in ``fed.engine``:

  * ``AsyncPolicy`` — the round re-timed by a virtual wall clock
    (``fed.clock``). Each round t:

      1. **Dispatch** — the topology selects with over-selection
         (``⌈m·(1+ε)⌉`` clients flat, edges hierarchical) and skips units
         still in flight (a real server does not re-dispatch a busy device).
         Flat selection scores the HeteRo-Select freshness term (Eq 7) on
         *clock-measured* staleness — elapsed virtual time since each
         client's update was last aggregated, in units of the reference
         round duration — via ``core.selection.make_async_selector``.
      2. **Train** — the dispatch trains in the regular executor (the
         batched vmap path stays the compute substrate); completions are
         *simulated events*: each unit's finished update is held back and
         scheduled on the clock at ``now + latency`` (``SystemProfile``
         multipliers × base × jitter).
      3. **Close** — the round closes at ``now + deadline``. Updates due by
         then — including stragglers dispatched in *earlier* rounds —
         aggregate now; later ones stay pending and carry forward as stale
         arrivals. If nothing arrived, the deadline extends to the next
         completion (a real federation waits rather than ship nothing).
      4. **Aggregate** — ``BufferedAggregator`` (FedBuff-style) applies the
         arrivals as parameter deltas against the global version each unit
         trained on, down-weighted polynomially in staleness:
         w_i ∝ (1+τ_i)^(−a).

  * ``BufferedAggregator`` — implements the ``Aggregator`` protocol
    (registered as ``"fedbuff"``), so it also composes with the synchronous
    policy, where every update has τ = 0 and it degenerates to FedAvg.

Equivalence contract: with equal latencies, ``deadline=∞`` and ``ε = 0``
the async run replays the synchronous run — same selector draws (the
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
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.fed import server as fed_server
from repro.fed.clock import Completion, LatencyModel, VirtualClock
from repro.fed.engine import (
    Aggregator,
    CohortUpdates,
    FedAvg,
    FederatedEngine,
    FederatedSpec,
    RoundContext,
    RoundPart,
    Update,
    cohort_mean,
    register_aggregator,
)


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

    The deadline close of ``AsyncPolicy``, whatever the topology: arrivals
    are client updates (flat) or edge aggregates (hierarchical), and
    ``in_flight`` is indexed by whatever ``Completion.client`` holds:

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
    """The async-mode aggregator contract, applied where the policy is chosen.

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


class BufferedAggregator(Aggregator):
    """FedBuff-style buffered aggregation with polynomial staleness discount.

    ``reduce`` consumes delta-form cohorts (``CohortUpdates.delta_list`` +
    ``staleness``): each arrival is a parameter delta against the global
    version its client trained on, weighted w_i ∝ (1+τ_i)^(−a) — times the
    data-size weight when the spec's ``cohort_weights`` provided one — and
    applied as one fused step (``fed.server.apply_weighted_deltas``).

    Under the synchronous policy (param-form cohorts) every update has
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
        delta = fed_server.params_delta_f32(cohort_mean(cohort), global_params)
        return fed_server.apply_weighted_deltas(
            global_params, [delta], jnp.ones((1,), jnp.float32),
            server_lr=self.server_lr)


@register_aggregator("fedbuff")
def _make_fedbuff(spec: FederatedSpec) -> BufferedAggregator:
    acfg = spec.async_cfg or AsyncConfig()
    return BufferedAggregator(staleness_power=acfg.staleness_power,
                              server_lr=acfg.server_lr)


class AsyncPolicy(RoundPart):
    """Round policy 'async' (module docstring): the topology's completions —
    each client of a flat cohort, or each edge — are scheduled on the
    virtual clock; the round closes at the deadline and the aggregator
    reduces the arrivals as deltas. The clock with its pending completions,
    the in-flight mask and the straggler and staleness series checkpoint
    through ``extra_state``, so a killed async run resumes bitwise."""

    name = "async"

    def __init__(self, eng: FederatedEngine):
        spec = eng.spec
        self.eng = eng
        self.acfg: AsyncConfig = spec.async_cfg or AsyncConfig()
        # (K,) per-client round-time multipliers from whatever the spec gave.
        k, mult = spec.data.num_clients, np.ones(spec.data.num_clients)
        if spec.system is not None:
            speeds = getattr(spec.system, "speeds", None)
            mult = np.asarray(speeds() if callable(speeds) else spec.system,
                              np.float64)
            if mult.shape != (k,):
                raise ValueError(
                    f"system profile must yield ({k},) multipliers, "
                    f"got shape {mult.shape}")
        self.latency = LatencyModel(mult, base=self.acfg.base_latency,
                                    jitter=self.acfg.jitter)
        self.clock: Optional[VirtualClock] = None
        self.in_flight: Optional[np.ndarray] = None
        eng.aggregator = upgrade_async_aggregator(eng.aggregator, self.acfg)

    def over_select(self, m: int, cap: int) -> int:
        """⌈m·(1+ε)⌉ (at most ``cap``): dispatch more so the deadline still
        harvests ~m updates when stragglers miss it."""
        return min(cap, int(math.ceil(m * (1.0 + self.acfg.over_select_frac))))

    def start(self) -> None:
        self.clock = VirtualClock()
        self.in_flight = np.zeros(self.eng.topology.num_units, bool)
        self.wall_clock: List[float] = []
        self.round_staleness: List[float] = []
        self.stragglers_carried = 0
        self.updates_dropped = 0

    def dispatch(self, t: int, out: List[Update]) -> None:
        """Hold the updates back: schedule each completion on the clock, at
        the slowest latency of the clients it carries."""
        eng = self.eng
        self.dispatch_time = self.clock.now
        if not out:
            return
        lat = self.latency.sample(np.concatenate([u.clients for u in out]),
                                  eng.rng)
        ends = np.cumsum([len(u.clients) for u in out])
        for u, u_lat in zip(out, np.split(lat, ends[:-1])):
            u.delta = fed_server.params_delta_f32(u.params, eng.params)
            u.params = None  # the anchor-relative delta is what travels
            self.clock.schedule(float(u_lat.max()), u.unit, t, payload=u)
            self.in_flight[u.unit] = True

    def reduce(self, ctx: RoundContext, t: int, out: List[Update],
               span_args: Dict[str, Any]) -> List[Update]:
        """Close the round at the deadline and aggregate what arrived; late
        updates carry forward as stale arrivals."""
        eng = self.eng
        kept, dropped = drain_due_arrivals(self.clock, self.acfg, t,
                                           self.dispatch_time, self.in_flight)
        stale = np.asarray([t - ev.dispatch_round for ev in kept], np.float32)
        stragglers = sum(1 for ev in kept if ev.dispatch_round < t)
        span_args.update(arrivals=int(len(kept)), stragglers=stragglers)
        arrived = [ev.payload for ev in kept]
        if arrived:
            eng.params = eng.aggregator.reduce(eng.params, CohortUpdates(
                mean_loss=np.asarray([u.losses.mean() for u in arrived],
                                     np.float32),
                update_sqnorm=np.asarray([u.sqnorms.mean() for u in arrived],
                                         np.float32),
                delta_list=[u.delta for u in arrived],
                staleness=stale,
                weights=np.asarray([u.weight for u in arrived], np.float32),
            ))
        self.updates_dropped += dropped
        self.stragglers_carried += stragglers
        self.wall_clock.append(self.clock.now)
        self.round_staleness.append(float(stale.mean()) if len(stale) else 0.0)
        ctx.sim_time = self.clock.now
        ctx.num_arrivals = len(kept)
        ctx.num_stragglers = stragglers
        return arrived

    def contribute(self, extras: Dict[str, Any]) -> None:
        extras.setdefault("wall_clock", np.asarray(self.wall_clock))
        extras.setdefault("round_staleness", np.asarray(self.round_staleness))

    # -- checkpoint / resume ----------------------------------------------
    #
    # Every pending completion persists its delta pytree as its own
    # schema-checked tree keyed by the event's seq, and the rest of its
    # update as the topology packs it.

    def extra_state(self):
        topo = self.eng.topology
        trees: Dict[str, Any] = {}
        arrays: Dict[str, np.ndarray] = {}
        pending: Dict[str, Any] = {}
        for ev in self.clock.pending():
            trees[f"pending/{ev.seq}"] = ev.payload.delta
            pending[str(ev.seq)] = topo.pack(ev.seq, ev.payload, arrays)
        arrays[topo.in_flight_key] = self.in_flight
        arrays["wall_clock"] = np.asarray(self.wall_clock, np.float64)
        arrays["round_staleness"] = np.asarray(self.round_staleness, np.float64)
        meta = {"clock": self.clock.state_dict(), "pending": pending,
                "stragglers_carried": self.stragglers_carried,
                "updates_dropped": self.updates_dropped}
        return trees, arrays, meta

    def extra_likes(self, meta):
        # Pending deltas share the params structure but are always f32
        # (params_delta_f32), whatever dtype the model params use.
        delta_like = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), self.eng.params)
        return {f"pending/{ev['seq']}": delta_like
                for ev in meta["extra"]["clock"]["events"]}

    def load_extra_state(self, trees, arrays, meta):
        extra, topo = meta["extra"], self.eng.topology
        unit = {int(ev["seq"]): int(ev["client"])
                for ev in extra["clock"]["events"]}
        payloads = {
            int(seq): topo.unpack(seq, unit[int(seq)], info, arrays,
                                  trees[f"pending/{seq}"])
            for seq, info in extra["pending"].items()
        }
        self.clock = VirtualClock()
        self.clock.load_state_dict(extra["clock"], payloads)
        self.in_flight = np.asarray(arrays[topo.in_flight_key], bool).copy()
        self.wall_clock = [float(x) for x in arrays["wall_clock"]]
        self.round_staleness = [float(x) for x in arrays["round_staleness"]]
        self.stragglers_carried = int(extra["stragglers_carried"])
        self.updates_dropped = int(extra["updates_dropped"])


class AsyncFederatedEngine(FederatedEngine):
    """What ``FederatedSpec.build()`` returns for round_policy='async' on the
    flat topology: ``FederatedEngine``'s round under ``AsyncPolicy``."""

    m_over = property(lambda self: self.topology.m)  # ⌈m·(1+ε)⌉, at most K
    stragglers_carried = property(lambda self: self.policy.stragglers_carried)
    updates_dropped = property(lambda self: self.policy.updates_dropped)
