"""Hierarchical two-tier federation: client → edge → cloud.

Cross-device federations are not flat: clients hang off edge aggregators
(base stations, hospital gateways, regional brokers) and only the edges talk
to the cloud over the expensive WAN. The client-selection survey (Fu et al.,
arXiv:2211.01549) and heterogeneity-guided sampling (Chen & Vikalo,
arXiv:2310.00198) both identify this grouped regime as where smart
participation compounds: selection happens *twice*, within edges and across
edges. ``HierarchicalEngine`` is that topology on the PR-3 plugin surface:

  1. **Partition** — the K clients split into E edge groups once per run
     (``fed.partition.partition_edges``): by label-skew similarity (clients
     with similar JS divergence share an edge — correlated geography) or at
     random. Every client belongs to exactly one edge.
  2. **Outer selection** — when ``HierarchyConfig.edges_per_round`` asks for
     fewer than E edges, the cloud scores edge *aggregates*: each edge's
     member rows pool into one pseudo-client (``core.state.pool_client_state``
     — observed-weighted mean losses, pooled diversity, mean participation,
     max recency) and the paper's score + softmax machinery runs on the
     (E,)-sized pooled state unchanged (``core.selection.edge_selection_probs``
     → host-side Gumbel-top-m over the idle edges).
  3. **Inner selection** — each active edge runs HeteRo-Select over an
     *edge-local* score table: its members' ``ClientState`` rows sliced out
     of the global SoA, so min-max loss normalization, fairness pressure and
     the softmax all renormalize within the edge — with the edge budget m_e
     (``edge_budgets``: an explicit ``FedConfig.edge_budget``, else
     ``num_selected`` distributed across edges proportionally to size,
     summing to ≤ m).
  4. **Two-stage aggregation** — each edge's cohort trains in one executor
     call (the batched vmap path stays the compute substrate) and reduces to
     the edge aggregate (``fed.server.fedavg_fused`` under the batched
     executor); the cloud then combines edge aggregates as size-weighted
     deltas (``fed.server.apply_weighted_deltas``). Only E aggregates cross
     the WAN per round instead of m client updates —
     ``FLResult.cloud_uploads`` is that axis, benchmarked against flat
     selection by ``benchmarks/table7_hierarchy.py``.

Both round policies compose (``FedConfig.round_policy``):

  * **sync** — edge rounds are barriers: every active edge's aggregate
    reaches the cloud in its dispatch round.
  * **async** — each edge is one event on the PR-4 ``VirtualClock``: the
    edge completes at the max of its cohort's latencies, the cloud closes
    the round at ``AsyncConfig.deadline``, and straggler edges carry forward
    as stale cloud arrivals discounted by the FedBuff weight (1+τ)^(−a)
    (``BufferedAggregator``). In-flight edges are not re-dispatched.

Degenerate-equivalence contract: with E = 1 and the full budget m the inner
selection *is* flat selection (same selector config, same key, the identity
slice of the state) and the single-edge cloud stage passes the edge
aggregate through bitwise — so the hierarchical run reproduces the flat
run's selection history exactly (pinned by tests/test_hierarchy.py).

With ``selector='heterosel_pallas'`` the inner stage runs as ONE segmented
kernel launch instead of E per-edge programs: the SoA state is relaid
edge-major into seg-aligned slices once at construction and
``kernels.score_select.segmented_score_probs`` scores + softmaxes every
edge's slice in its own grid program. Per-edge Gumbel-top-m sampling stays
host-dispatched on the same per-edge keys and (|edge|,) probability vectors
as the jnp path, so the selection history matches ``selector='heterosel'``
(pinned by tests/test_hierarchy.py).

``CheckpointHook`` composes with both policies: the cloud-upload series —
and in async mode the virtual clock with its in-flight edge cohorts — are
part of the versioned round snapshot via the engine's ``extra_state``
protocol, so a killed hierarchical run resumes bitwise
(tests/test_resume_matrix.py).

``availability`` masks (device churn) thread through the *inner* stage:
each edge's selector is wrapped with the flat engine's
``fed.availability.mask_selector`` over the edge-local mask columns (the
segmented heterosel_pallas path applies the same ``_remask`` host-side), so
offline members are never selected and a fully-offline edge simply yields
an empty cohort that round. The outer stage is unaffected — edges stay
schedulable; churn gates clients, not edges.

With ``selector='adaptive'`` the per-edge budgets stop being static: a
``core.adaptive.AdaptiveBudgets`` controller observes each round's mean
edge-cohort loss, EMA-smooths it (the Lemma-A.4 slow-move discipline) and
re-apportions ``num_selected`` across edges by utility-weighted largest
remainder — still Σ m_e ≤ m and m_e ≤ |edge| by construction. Budget moves
rebuild only the affected per-edge jitted selectors (cached by signature),
and the controller state rides the ``extra_state`` checkpoint protocol so
resumed runs stay bitwise.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.adaptive import AdaptiveBudgets
from repro.core.scoring import HeteRoScoreConfig
from repro.core.selection import (
    SelectorConfig,
    base_selector_name,
    dynamic_temperature,
    edge_selection_probs,
    make_selector,
    sample_clients,
)
from repro.core.state import pool_client_state, update_client_state
from repro import ckpt as repro_ckpt
from repro.fed import availability as fed_avail
from repro.fed import server as fed_server
from repro.fed.async_engine import (
    AsyncConfig,
    _resolve_multipliers,
    drain_due_arrivals,
    upgrade_async_aggregator,
)
from repro.fed.clock import LatencyModel, VirtualClock
from repro.fed.engine import (
    CohortUpdates,
    FedAvg,
    FederatedEngine,
    FederatedSpec,
    FLResult,
    RoundContext,
    WeightedFedAvg,
)
from repro.fed.partition import EdgePartition, partition_edges
from repro.obs.phases import phase


@dataclasses.dataclass(frozen=True)
class HierarchyConfig:
    """Knobs of the hierarchical round manager (spec field ``hier_cfg``).

    partition_mode:   how clients group into edges — 'similarity' (sorted by
                      label-skew JS divergence, contiguous blocks) or
                      'random' (seeded permutation).
    edges_per_round:  outer cross-edge selection budget E_sel; 0 ⇒ every
                      (idle) edge participates each round.
    partition_seed:   seed of the 'random' partition; None ⇒ ``fed.seed``.
    """

    partition_mode: str = "similarity"
    edges_per_round: int = 0
    partition_seed: Optional[int] = None

    def __post_init__(self):
        if self.edges_per_round < 0:
            raise ValueError("edges_per_round must be ≥ 0 (0 = all edges)")


def edge_budgets(num_selected: int, sizes: np.ndarray,
                 edge_budget: int = 0) -> np.ndarray:
    """(E,) inner selection budgets m_e.

    With an explicit ``edge_budget`` every edge gets ``min(edge_budget,
    |edge|)``. Otherwise the global budget m (``num_selected``) distributes
    across edges proportionally to edge size by largest remainder, capped at
    the edge size — so Σ m_e = min(m, K) ≤ m (the invariant
    tests/test_hierarchy.py pins) and the E=1 degenerate case gets exactly m.
    """
    sizes = np.asarray(sizes, np.int64)
    if edge_budget > 0:
        return np.minimum(edge_budget, sizes)
    total = int(min(num_selected, sizes.sum()))
    quota = total * sizes / max(int(sizes.sum()), 1)
    base = np.minimum(np.floor(quota).astype(np.int64), sizes)
    frac = quota - np.floor(quota)
    order = np.argsort(-frac, kind="stable")
    rem = total - int(base.sum())
    while rem > 0:
        progressed = False
        for e in order:
            if rem == 0:
                break
            if base[e] < sizes[e]:
                base[e] += 1
                rem -= 1
                progressed = True
        if not progressed:  # every edge at capacity (total == K)
            break
    return base


@dataclasses.dataclass
class EdgeCohort:
    """One edge's inner-round outcome on its way to the cloud."""

    edge: int
    selected: np.ndarray       # global client ids of the edge cohort
    losses: np.ndarray         # (m_e,) per-client mean local loss
    sqnorms: np.ndarray        # (m_e,) per-client ||Δw||²
    weight: float              # cloud combine weight (cohort size / |D| sum)
    avg_params: Any = None     # the edge aggregate (sync path)
    delta: Any = None          # f32 edge aggregate − dispatch anchor (async)


class HierarchicalEngine(FederatedEngine):
    """Two-tier rounds over the plugin surface (``FedConfig.topology``).

    Built by ``FederatedSpec.build()`` when the resolved topology is
    ``'hierarchical'``. Handles both round policies itself: sync edge
    barriers, or async edge events on a ``VirtualClock`` with deadline-closed
    cloud rounds — flat mode's ``AsyncFederatedEngine`` is *not* stacked
    underneath, because the unit of cloud arrival here is an edge aggregate,
    not a client update.
    """

    def __init__(self, spec: FederatedSpec):
        super().__init__(spec)
        fed = spec.fed
        self._avail = (None if spec.availability is None
                       else np.asarray(spec.availability, bool))
        if fed.edge_count < 1:
            raise ValueError(
                "topology='hierarchical' requires FedConfig.edge_count ≥ 1 "
                f"(got {fed.edge_count}); set edge_count=E or topology='flat'")
        self.hcfg: HierarchyConfig = spec.hier_cfg or HierarchyConfig()
        self.policy = spec.resolved_round_policy

        seed = (fed.seed if self.hcfg.partition_seed is None
                else self.hcfg.partition_seed)
        self.partition: EdgePartition = partition_edges(
            np.asarray(spec.data.label_js), fed.edge_count,
            mode=self.hcfg.partition_mode, seed=seed)
        self.edge_count = self.partition.edge_count
        self._members = self.partition.member_lists()
        self._assignment = jnp.asarray(self.partition.assignment)
        self.budgets = edge_budgets(
            fed.num_selected, self.partition.sizes, fed.edge_budget)

        self._score_cfg = spec.score_cfg or HeteRoScoreConfig()
        base_sel = spec.sel_cfg or SelectorConfig(num_selected=fed.num_selected)
        self._base_sel = base_sel
        base_name = base_selector_name(self.selector_name)
        # Outer-stage selector semantics follow the configured selector
        # family so hierarchical baselines stay uncontaminated: HeteRo
        # variants score pooled edges (additive or multiplicative to match;
        # 'adaptive' and 'filtered' fall back to their HeteRo base scoring —
        # pooled pseudo-clients have no per-client pool to filter and the
        # temperature controller is an inner-stage signal), 'random' samples
        # edges uniformly, and the greedy baselines (oort, power_of_choice)
        # have no defined edge-level analogue — loud error rather than a
        # silently HeteRo-biased edge choice.
        outer_active = 0 < self.hcfg.edges_per_round < self.edge_count
        if outer_active and base_name in ("oort", "power_of_choice"):
            raise ValueError(
                f"selector={self.selector_name!r} has no edge-level analogue "
                "for the outer cross-edge stage; with edges_per_round < "
                "edge_count use a 'heterosel*' selector or 'random' (or set "
                "edges_per_round=0 to dispatch every edge)")
        self._outer_uniform = base_name == "random"
        self._outer_sel_cfg = (
            dataclasses.replace(base_sel, additive=False)
            if base_name == "heterosel_mult" else base_sel)
        # The heterogeneity-guided 'adaptive' selector also retunes the edge
        # budgets online (module docstring): mean edge-cohort losses feed an
        # AdaptiveBudgets controller whose re-apportionment replaces the
        # static size-proportional split after the first observation.
        self._budget_ctl: Optional[AdaptiveBudgets] = None
        if base_name == "adaptive":
            if fed.edge_budget > 0:
                raise ValueError(
                    "selector='adaptive' retunes per-edge budgets online "
                    "from the global num_selected; an explicit "
                    "FedConfig.edge_budget conflicts with that — unset it "
                    "or use a non-adaptive selector")
            self._budget_ctl = AdaptiveBudgets(fed.num_selected,
                                               self.partition.sizes)
        # Inner-selection machinery. heterosel_pallas scores every edge in
        # ONE segmented kernel launch (_seg_probs below); everything else
        # gets one jitted per-edge selector per distinct (edge size, budget)
        # signature — partition_edges balances sizes to within one client,
        # so E edges share at most a couple of compiled programs instead of
        # tracing one per edge. Shapes are static across rounds; budget
        # retunes rebuild through the persistent _sel_cache, so oscillating
        # budgets never recompile twice.
        self._edge_select: Dict[int, Any] = {}
        self._sel_cache: Dict[Any, Any] = {}
        self._seg_probs: Optional[Any] = None
        if self.selector_name == "heterosel_pallas":
            self._init_segmented_selection(base_sel)
        else:
            self._build_edge_selectors()

        if self.policy == "async":
            self.acfg: AsyncConfig = spec.async_cfg or AsyncConfig()
            mult = _resolve_multipliers(spec.system, spec.data.num_clients)
            self.latency = LatencyModel(mult, base=self.acfg.base_latency,
                                        jitter=self.acfg.jitter)
            self.aggregator = upgrade_async_aggregator(self.aggregator,
                                                       self.acfg)
        else:
            if spec.async_cfg is not None or spec.system is not None:
                raise ValueError(
                    "async_cfg/system are only consumed by "
                    "round_policy='async'; the sync engine has no wall clock "
                    "to apply them to")
            if not isinstance(self.aggregator, (FedAvg, WeightedFedAvg)):
                raise ValueError(
                    f"aggregator {getattr(self.aggregator, 'name', self.aggregator)!r} "
                    "does not compose with the hierarchical cloud stage "
                    "(edge aggregates combine as weighted deltas, not a "
                    "cohort reduce); use 'fedavg' or 'fedavg_weighted'")

    def _init_segmented_selection(self, base_sel: SelectorConfig) -> None:
        """The heterosel_pallas inner-stage fast path: one segmented kernel.

        Lays the (K,) SoA state out edge-major once — edge e owns the
        seg-aligned slice ``[e·seg, e·seg + |edge e|)`` of a (E·seg,)
        permutation, padding slots masked inside the kernel — so scoring +
        softmax for ALL edges is a single ``segmented_score_probs`` launch
        (grid=(E,)) instead of E gather + jnp programs per round.
        """
        from repro.kernels import ops as kernel_ops  # deferred: pallas optional
        from repro.kernels.score_select import LANE

        seg = -(-max(int(self.partition.sizes.max()), 1) // LANE) * LANE
        perm = np.zeros(self.edge_count * seg, np.int64)
        for e in range(self.edge_count):
            members = self._members[e]
            perm[e * seg:e * seg + len(members)] = members
        self._seg = seg
        seg_perm = jnp.asarray(perm)
        seg_sizes = jnp.asarray(self.partition.sizes, jnp.int32)
        score_cfg = self._score_cfg
        interpret = kernel_ops.kernel_interpret()

        def segmented_probs(state, round_idx):
            sstate = jax.tree_util.tree_map(lambda x: x[seg_perm], state)
            tau = dynamic_temperature(round_idx, base_sel)
            probs, _ = kernel_ops.heterosel_probs_segmented(
                sstate, seg_sizes,
                round_idx=jnp.asarray(round_idx, jnp.float32), tau=tau,
                cfg=score_cfg, seg=seg, interpret=interpret)
            return probs

        self._seg_probs = jax.jit(segmented_probs)

    def _build_edge_selectors(self) -> None:
        """(Re)bind per-edge jitted selectors to the current budgets.

        Called at construction, after every `AdaptiveBudgets` move, and on
        checkpoint restore. Compiled programs are cached in ``_sel_cache``:
        without availability masks edges share one program per (size,
        budget) signature; with them each edge closes over its own mask
        columns, so the cache keys on (edge, budget) instead. Zero-budget
        edges are unbound (``_idle_edges`` never offers them).
        """
        self._edge_select = {}
        for e in range(self.edge_count):
            b = int(self.budgets[e])
            if b == 0:
                continue
            cache_key = ((len(self._members[e]), b) if self._avail is None
                         else ("avail", e, b))
            if cache_key not in self._sel_cache:
                cfg_e = dataclasses.replace(self._base_sel, num_selected=b)
                select = make_selector(self.selector_name, cfg_e,
                                       self._score_cfg)
                if self._avail is not None:
                    # Same semantics as the flat engine's churn path: zero
                    # offline mass, renormalize, resample inside the edge.
                    select = fed_avail.mask_selector(
                        select,
                        jnp.asarray(self._avail[:, self._members[e]]),
                        num_selected=b)
                self._sel_cache[cache_key] = jax.jit(select)
            self._edge_select[e] = self._sel_cache[cache_key]

    # -- lifecycle ---------------------------------------------------------

    def run(self) -> FLResult:
        self.cloud_uploads: List[int] = []
        if self.policy == "async":
            self.clock = VirtualClock()
            self._edge_in_flight = np.zeros(self.edge_count, bool)
            self.wall_clock: List[float] = []
            self.round_staleness: List[float] = []
            self.stragglers_carried = 0
            self.updates_dropped = 0
        return super().run()

    # -- the two selection stages ------------------------------------------

    def _idle_edges(self) -> List[int]:
        busy = (self._edge_in_flight if self.policy == "async"
                else np.zeros(self.edge_count, bool))
        return [e for e in range(self.edge_count)
                if self.budgets[e] > 0 and not busy[e]]

    def _choose_edges(self, sk: jax.Array, t: int, idle: List[int]) -> List[int]:
        """Outer cross-edge selection over the idle edges.

        When the outer budget covers every idle edge no randomness is drawn —
        which is what keeps the E=1 degenerate case on the flat engine's
        exact RNG stream. Otherwise edges are scored on their pooled
        pseudo-client state and sampled Gumbel-top-m host-side (the idle set
        varies per round, so the draw cannot be a fixed-shape jitted op).

        In async mode ``AsyncConfig.over_select_frac`` applies at the edge
        tier: ⌈E_sel·(1+ε)⌉ edges dispatch so the cloud deadline still
        harvests ~E_sel aggregates when a straggler edge misses it — the
        edge-level mirror of flat async's client over-selection.
        """
        e_sel = self.hcfg.edges_per_round or self.edge_count
        if self.policy == "async":
            e_sel = int(math.ceil(e_sel * (1.0 + self.acfg.over_select_frac)))
        if e_sel >= len(idle):
            return list(idle)
        if self._outer_uniform:  # selector='random': uniform edge choice too
            probs = np.full(self.edge_count, 1.0 / self.edge_count)
        else:
            pooled = pool_client_state(self.state, self._assignment,
                                       self.edge_count)
            probs = np.asarray(edge_selection_probs(
                pooled, jnp.int32(t), self._outer_sel_cfg, self._score_cfg),
                np.float64)
        g = np.asarray(jax.random.gumbel(
            jax.random.fold_in(sk, self.edge_count), (self.edge_count,)),
            np.float64)
        pert = np.log(probs + 1e-30) + g
        eligible = np.zeros(self.edge_count, bool)
        eligible[idle] = True
        pert[~eligible] = -np.inf
        top = np.argsort(-pert, kind="stable")[:e_sel]
        return sorted(int(e) for e in top)

    def _inner_keys(self, sk: jax.Array) -> Dict[int, jax.Array]:
        if self.edge_count == 1:
            # Degenerate contract: one edge consumes the round key exactly
            # like the flat engine's single selector call.
            return {0: sk}
        split = jax.random.split(sk, self.edge_count)
        return {e: split[e] for e in range(self.edge_count)}

    def _inner_select(self, active: List[int], keys: Dict[int, jax.Array],
                      t: int) -> List[tuple]:
        """Inner per-edge selection: (edge, global cohort ids) pairs.

        The segmented fast path (heterosel_pallas) scores every edge in one
        kernel launch, then samples each active edge's cohort host-side with
        the SAME per-edge key and (|edge|,) probability vector the jnp path
        would use — which is what keeps the selection histories equal.
        """
        picks: List[tuple] = []
        if self._seg_probs is not None:
            probs_all = np.asarray(self._seg_probs(self.state, jnp.int32(t)))
            for e in active:
                members = self._members[e]
                probs_e = jnp.asarray(
                    probs_all[e * self._seg:e * self._seg + len(members)])
                if self._avail is not None:
                    # Host-side mirror of mask_selector: the same _remask
                    # (fold_in(key, 1) draw) on the kernel's probabilities,
                    # so the cohort matches the jnp churn path exactly.
                    mask_local, _ = fed_avail._remask(
                        keys[e], probs_e,
                        jnp.asarray(self._avail[t][members]),
                        int(self.budgets[e]))
                else:
                    mask_local = sample_clients(keys[e], probs_e,
                                                int(self.budgets[e]))
                sel_local = np.flatnonzero(np.asarray(mask_local))
                if len(sel_local):
                    picks.append((e, members[sel_local]))
            return picks
        for e in active:
            members = self._members[e]
            idx = jnp.asarray(members)
            estate = jax.tree_util.tree_map(lambda x: x[idx], self.state)
            mask_local, _ = self._edge_select[e](keys[e], estate, jnp.int32(t))
            sel_local = np.flatnonzero(np.asarray(mask_local))
            if len(sel_local):
                picks.append((e, members[sel_local]))
        return picks

    def _inner_execute(self, picks: List[tuple]) -> List[EdgeCohort]:
        """One executor call per selected edge cohort."""
        out: List[EdgeCohort] = []
        for e, sel_global in picks:
            with phase("edge_execute", recorder=self.tracer, track="edges",
                       args={"edge": int(e), "cohort": int(len(sel_global))}):
                weights = self.aggregator.cohort_weights(sel_global,
                                                         self.spec.data)
                cohort = self.executor.run_round(self.params, sel_global,
                                                 self.rng, weights=weights)
                self.wire_total += cohort.wire_bytes
                self.raw_total += cohort.raw_bytes
                ew = (float(len(sel_global)) if weights is None
                      else float(np.sum(np.asarray(weights, np.float64))))
                losses = np.asarray(cohort.mean_loss, np.float32)
                sqnorms = np.asarray(cohort.update_sqnorm, np.float32)
                with phase("aggregate"):
                    avg_params = self.aggregator._mean(cohort)
                out.append(EdgeCohort(edge=e, selected=sel_global, losses=losses,
                                      sqnorms=sqnorms, weight=ew,
                                      avg_params=avg_params))
        return out

    # -- observation fold (shared by both policies) ------------------------

    def _fold_observations(self, ctx: RoundContext, t: int,
                           cohorts: List[EdgeCohort],
                           dispatched_mask: Optional[np.ndarray] = None) -> None:
        k = self.spec.data.num_clients
        mask = np.zeros(k, bool)
        obs_loss = np.zeros(k, np.float32)
        obs_sqnorm = np.zeros(k, np.float32)
        all_losses: List[np.ndarray] = []
        for c in cohorts:
            mask[c.selected] = True
            obs_loss[c.selected] = c.losses
            obs_sqnorm[c.selected] = c.sqnorms
            all_losses.append(c.losses)
        if mask.any():
            self.state = update_client_state(
                self.state,
                round_idx=jnp.int32(t),
                selected_mask=jnp.asarray(mask),
                observed_loss=jnp.asarray(obs_loss),
                observed_sqnorm=jnp.asarray(obs_sqnorm),
            )
        ctx.mask = mask if dispatched_mask is None else dispatched_mask
        ctx.selected = np.flatnonzero(ctx.mask)
        ctx.obs_loss = obs_loss
        ctx.obs_sqnorm = obs_sqnorm
        ctx.train_loss = (float(np.concatenate(all_losses).mean())
                          if all_losses else 0.0)
        if self._budget_ctl is not None and cohorts:
            self._retune_budgets(cohorts)

    def _retune_budgets(self, cohorts: List[EdgeCohort]) -> None:
        """Feed one round's mean edge-cohort losses to `AdaptiveBudgets`;
        rebind the per-edge selectors only when the apportionment moved.

        NaN marks edges that reported nothing this round — the controller
        keeps their previous smoothed estimate (never zeroes them out).
        Deterministic given the run's observations, so the selection
        history stays seed-deterministic and resume-bitwise (the
        controller state persists via ``extra_state``).
        """
        util = np.full(self.edge_count, np.nan)
        for c in cohorts:
            util[c.edge] = float(np.asarray(c.losses, np.float64).mean())
        new_budgets = self._budget_ctl.observe_round(util)
        if not np.array_equal(new_budgets, self.budgets):
            self.budgets = new_budgets
            self._build_edge_selectors()

    # -- rounds ------------------------------------------------------------

    def _run_round(self, ctx: RoundContext, t: int, eval_batch: Any) -> None:
        if self.policy == "async":
            self._run_round_async(ctx, t, eval_batch)
        else:
            self._run_round_sync(ctx, t, eval_batch)

    def _select_phase(self, ctx: RoundContext, t: int) -> List[tuple]:
        """Outer (edge) then inner (per-edge client) selection; returns the
        (edge, global cohort ids) picks."""
        tr = self.tracer
        with phase("select", ctx, tr, args={"round": t}):
            self.key, sk = jax.random.split(self.key)
            with phase("outer_select", recorder=tr, args={"round": t}) as outer:
                active = self._choose_edges(sk, t, self._idle_edges())
                outer.args["edges"] = int(len(active))
            with phase("inner_select", recorder=tr, args={"round": t}):
                picks = self._inner_select(active, self._inner_keys(sk), t)
        return picks

    def _run_round_sync(self, ctx: RoundContext, t: int, eval_batch: Any) -> None:
        spec, tr = self.spec, self.tracer
        picks = self._select_phase(ctx, t)
        with phase("execute", ctx, tr, args={"round": t, "edges": int(len(picks))}):
            cohorts = self._inner_execute(picks)

        with phase("aggregate", ctx, tr,
                   args={"round": t, "cloud_uploads": int(len(cohorts))}):
            if len(cohorts) == 1:
                # The weighted mean of one edge aggregate is that aggregate —
                # taken bitwise, which is what pins the E=1 flat-equivalence
                # contract (no f32 round-trip through the delta form).
                self.params = cohorts[0].avg_params
            elif cohorts:
                deltas = [fed_server.params_delta_f32(c.avg_params, self.params)
                          for c in cohorts]
                w = jnp.asarray([c.weight for c in cohorts], jnp.float32)
                self.params = fed_server.apply_weighted_deltas(
                    self.params, deltas, w)
            self.cloud_uploads.append(len(cohorts))

        with phase("state_update", recorder=tr, args={"round": t}):
            self._fold_observations(ctx, t, cohorts)
        with phase("eval", recorder=tr, args={"round": t}):
            ctx.metric = self.eval_fn(spec.model, self.params, eval_batch)
        ctx.sim_time = float(t + 1)  # sync rounds cost "1" on the time axis
        self._rounds_done = t + 1

    def _run_round_async(self, ctx: RoundContext, t: int, eval_batch: Any) -> None:
        spec, acfg, tr = self.spec, self.acfg, self.tracer
        dispatch_time = self.clock.now

        # 1.–2. Dispatch idle edges; each trains now but its aggregate
        # arrives at the cloud after the max of its cohort's latencies
        # (the edge is an internal barrier).
        picks = self._select_phase(ctx, t)
        dispatched = np.zeros(spec.data.num_clients, bool)
        with phase("execute", ctx, tr, args={"round": t, "edges": int(len(picks))}):
            for c in self._inner_execute(picks):
                c.delta = fed_server.params_delta_f32(c.avg_params, self.params)
                c.avg_params = None  # the anchor-relative delta is what travels
                lat = float(self.latency.sample(c.selected, self.rng).max())
                self.clock.schedule(lat, c.edge, t, payload=c)
                self._edge_in_flight[c.edge] = True
                dispatched[c.selected] = True

        # 3. Close the cloud round at the deadline (the shared flat-async
        # semantics — drain_due_arrivals); straggler edges carry forward as
        # stale arrivals.
        # 4. Buffered aggregation of the arrived edge aggregates.
        with phase("aggregate", ctx, tr, args={"round": t}) as agg:
            kept, dropped = drain_due_arrivals(self.clock, acfg, t, dispatch_time,
                                               self._edge_in_flight)
            self.updates_dropped += dropped
            stale = np.asarray([t - ev.dispatch_round for ev in kept], np.float32)
            n_stragglers = sum(1 for ev in kept if ev.dispatch_round < t)
            agg.args.update(cloud_uploads=int(len(kept)), stragglers=n_stragglers)
            arrivals = [ev.payload for ev in kept]
            if kept:
                agg_cohort = CohortUpdates(
                    mean_loss=np.asarray([c.losses.mean() for c in arrivals],
                                         np.float32),
                    update_sqnorm=np.asarray([c.sqnorms.mean() for c in arrivals],
                                             np.float32),
                    delta_list=[c.delta for c in arrivals],
                    staleness=stale,
                    weights=np.asarray([c.weight for c in arrivals], np.float32),
                )
                self.params = self.aggregator.reduce(self.params, agg_cohort)
            self.cloud_uploads.append(len(kept))

        with phase("state_update", recorder=tr, args={"round": t}):
            self._fold_observations(ctx, t, arrivals, dispatched_mask=dispatched)

        self.stragglers_carried += n_stragglers
        self.wall_clock.append(self.clock.now)
        self.round_staleness.append(float(stale.mean()) if len(stale) else 0.0)
        ctx.sim_time = self.clock.now
        ctx.num_arrivals = len(kept)
        ctx.num_stragglers = n_stragglers
        with phase("eval", recorder=tr, args={"round": t}):
            ctx.metric = self.eval_fn(spec.model, self.params, eval_batch)
        self._rounds_done = t + 1

    def _result(self, extras) -> FLResult:
        extras.setdefault("cloud_uploads",
                          np.asarray(self.cloud_uploads, np.int64))
        if self.policy == "async":
            extras.setdefault("wall_clock", np.asarray(self.wall_clock))
            extras.setdefault("round_staleness",
                              np.asarray(self.round_staleness))
        return super()._result(extras)

    # -- checkpoint / resume ----------------------------------------------
    #
    # The edge partition itself is deterministic from the spec (label_js +
    # edge_count + partition mode/seed), so it is rebuilt, not persisted —
    # only its shape is stamped into the snapshot as a sanity check. What
    # does persist via the extra_state protocol: the cloud-upload series,
    # and in async mode the virtual clock with each in-flight EdgeCohort
    # (delta pytree as its own schema-checked tree; cohort ids / losses /
    # sqnorms as per-seq arrays) plus the in-flight edge mask and the
    # wall-clock series. The snapshot kind embeds the round policy, so an
    # async/hierarchical snapshot never restores into a sync engine.

    @property
    def snapshot_kind(self) -> str:
        return f"{self.policy}/hierarchical"

    def extra_state(self):
        trees: Dict[str, Any] = {}
        arrays: Dict[str, np.ndarray] = {
            "cloud_uploads": np.asarray(self.cloud_uploads, np.int64),
        }
        meta: Dict[str, Any] = {"edge_count": self.edge_count}
        if self._budget_ctl is not None:
            arrays["budgets"] = np.asarray(self.budgets, np.int64)
            util = self._budget_ctl.utilities
            if util is not None:
                arrays["budget_util"] = util
        if self.policy == "async":
            pending_meta: Dict[str, Any] = {}
            for ev in self.clock.pending():
                c = ev.payload
                trees[f"pending/{ev.seq}"] = c.delta
                arrays[f"pending_sel/{ev.seq}"] = np.asarray(c.selected,
                                                             np.int64)
                arrays[f"pending_loss/{ev.seq}"] = np.asarray(c.losses,
                                                              np.float32)
                arrays[f"pending_sqnorm/{ev.seq}"] = np.asarray(c.sqnorms,
                                                                np.float32)
                pending_meta[str(ev.seq)] = {"edge": c.edge,
                                             "weight": c.weight}
            arrays["edge_in_flight"] = self._edge_in_flight
            arrays["wall_clock"] = np.asarray(self.wall_clock, np.float64)
            arrays["round_staleness"] = np.asarray(self.round_staleness,
                                                   np.float64)
            meta.update(clock=self.clock.state_dict(), pending=pending_meta,
                        stragglers_carried=self.stragglers_carried,
                        updates_dropped=self.updates_dropped)
        return trees, arrays, meta

    def extra_likes(self, meta):
        extra = meta["extra"]
        if extra.get("edge_count") != self.edge_count:
            raise repro_ckpt.CheckpointMismatchError(
                f"snapshot was written with edge_count="
                f"{extra.get('edge_count')}, this engine partitions into "
                f"{self.edge_count} edges — resume with the same "
                "FedConfig.edge_count")
        if self.policy != "async":
            return {}
        # In-flight edge deltas share the params structure but are always
        # f32 (params_delta_f32), whatever dtype the model params use.
        delta_like = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), self.params)
        return {f"pending/{ev['seq']}": delta_like
                for ev in extra["clock"]["events"]}

    def load_extra_state(self, trees, arrays, meta):
        extra = meta["extra"]
        self.cloud_uploads = [int(x) for x in arrays["cloud_uploads"]]
        if self._budget_ctl is not None:
            util = arrays.get("budget_util")
            self._budget_ctl.load_state_dict(
                {"util": None if util is None else np.asarray(util)})
            self.budgets = np.asarray(arrays["budgets"], np.int64)
            if self._seg_probs is None:
                self._build_edge_selectors()
        if self.policy != "async":
            return
        payloads = {
            int(seq): EdgeCohort(
                edge=int(info["edge"]),
                selected=np.asarray(arrays[f"pending_sel/{seq}"], np.int64),
                losses=np.asarray(arrays[f"pending_loss/{seq}"], np.float32),
                sqnorms=np.asarray(arrays[f"pending_sqnorm/{seq}"],
                                   np.float32),
                weight=float(info["weight"]),
                avg_params=None,
                delta=trees[f"pending/{seq}"])
            for seq, info in extra["pending"].items()
        }
        self.clock = VirtualClock()
        self.clock.load_state_dict(extra["clock"], payloads)
        self._edge_in_flight = np.asarray(arrays["edge_in_flight"],
                                          bool).copy()
        self.wall_clock = [float(x) for x in arrays["wall_clock"]]
        self.round_staleness = [float(x) for x in arrays["round_staleness"]]
        self.stragglers_carried = int(extra["stragglers_carried"])
        self.updates_dropped = int(extra["updates_dropped"])
