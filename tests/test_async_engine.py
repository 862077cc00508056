"""Async federation subsystem tests (ISSUE 4 tentpole).

Covers the acceptance criteria:
  * virtual-clock determinism — event ordering is (time, insertion-seq);
    a fixed seed yields an identical latency/event sequence;
  * staleness weights — FedBuff's polynomial discount, normalization inside
    the fused delta application, FedAvg degeneration at τ = 0;
  * sync-vs-async equivalence — equal latencies + deadline ∞ + ε = 0
    replays the synchronous selection stream and lands within ±1% final
    accuracy at quickstart scale;
  * deadline semantics — stragglers miss the round, stay in flight, and
    carry forward as staleness-discounted arrivals; over-selection
    dispatches ⌈m·(1+ε)⌉; no update is silently lost.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import tiny_model
from repro.configs.base import FedConfig
from repro.core.scoring import HeteRoScoreConfig, compute_scores, staleness_factor
from repro.core.selection import SelectorConfig, make_async_selector
from repro.core.state import init_client_state, staleness as state_staleness
from repro.data import make_vision_data
from repro.fed import (
    AsyncConfig,
    AsyncFederatedEngine,
    BufferedAggregator,
    ExecutorCompatError,
    FederatedSpec,
    LatencyModel,
    RoundHook,
    VirtualClock,
    staleness_weights,
)
from repro.fed import server as fs


@pytest.fixture(scope="module")
def async_quickstart_setup():
    """The acceptance-criterion scale: examples/quickstart.py federation."""
    fed = FedConfig(num_clients=12, participation=0.5, rounds=6,
                    local_epochs=2, local_batch=16, lr=0.3, mu=0.1,
                    dirichlet_alpha=0.1, seed=0)
    data = make_vision_data(fed, train_per_class=48, test_per_class=16, noise=0.3)
    return fed, data, tiny_model()


@pytest.fixture(scope="module")
def async_small_setup():
    fed = FedConfig(num_clients=6, participation=0.5, rounds=4, local_epochs=1,
                    local_batch=8, lr=0.2, mu=0.1, dirichlet_alpha=0.1, seed=0)
    data = make_vision_data(fed, train_per_class=24, test_per_class=8, noise=0.3)
    return fed, data, tiny_model()


class TestVirtualClock:
    def test_events_pop_in_time_then_insertion_order(self):
        clk = VirtualClock()
        clk.schedule(3.0, client=0, dispatch_round=0)
        clk.schedule(1.0, client=1, dispatch_round=0)
        clk.schedule(1.0, client=2, dispatch_round=0)  # same time, later seq
        clk.schedule(2.0, client=3, dispatch_round=0)
        out = clk.pop_due(10.0)
        assert [ev.client for ev in out] == [1, 2, 3, 0]
        assert clk.now == 10.0 and len(clk) == 0

    def test_deadline_leaves_late_events_pending(self):
        clk = VirtualClock()
        clk.schedule(1.0, client=0, dispatch_round=0)
        clk.schedule(5.0, client=1, dispatch_round=0)
        due = clk.pop_due(2.0)
        assert [ev.client for ev in due] == [0]
        assert len(clk) == 1 and clk.peek_time() == 5.0
        assert clk.latest_time() == 5.0
        # the clock advances to the deadline even when nothing was due
        assert clk.pop_due(3.0) == [] and clk.now == 3.0

    def test_time_is_monotone_and_delay_validated(self):
        clk = VirtualClock(start=4.0)
        clk.advance_to(2.0)
        assert clk.now == 4.0
        with pytest.raises(ValueError, match="≥ 0"):
            clk.schedule(-1.0, client=0, dispatch_round=0)

    def test_fixed_seed_identical_event_sequence(self):
        def sequence(seed):
            rng = np.random.default_rng(seed)
            lm = LatencyModel(np.array([1.0, 2.0, 4.0]), base=0.5, jitter=0.3)
            clk = VirtualClock()
            for t in range(5):
                for c, lat in enumerate(lm.sample(np.arange(3), rng)):
                    clk.schedule(lat, client=c, dispatch_round=t)
                for ev in clk.pop_due(clk.now + 1.0):
                    pass
            return [(ev.time, ev.client) for ev in clk.drain()]

        assert sequence(0) == sequence(0)
        assert sequence(0) != sequence(1)

    def test_latency_model_validation_and_determinism(self):
        with pytest.raises(ValueError, match="positive"):
            LatencyModel(np.array([1.0, -2.0]))
        with pytest.raises(ValueError, match="RNG"):
            LatencyModel(np.ones(3), jitter=0.5).sample(np.arange(3))
        lm = LatencyModel(np.array([1.0, 10.0]), base=2.0)
        np.testing.assert_allclose(lm.sample(np.array([1, 0])), [20.0, 2.0])
        assert lm.reference_time() == pytest.approx(2.0 * 5.5)


class TestStalenessWeights:
    def test_polynomial_discount(self):
        tau = np.array([0.0, 1.0, 3.0, 15.0])
        w = staleness_weights(tau, power=0.5)
        np.testing.assert_allclose(w, (1.0 + tau) ** -0.5)
        assert w[0] == 1.0 and (np.diff(w) < 0).all()
        np.testing.assert_allclose(staleness_weights(tau, power=0.0), 1.0)

    def test_apply_weighted_deltas_normalizes(self):
        g = {"w": jnp.zeros(3)}
        deltas = [{"w": jnp.ones(3)}, {"w": jnp.full(3, 3.0)}]
        out = fs.apply_weighted_deltas(g, deltas, jnp.asarray([1.0, 1.0]))
        np.testing.assert_allclose(np.asarray(out["w"]), 2.0, atol=1e-6)
        out2 = fs.apply_weighted_deltas(g, deltas, jnp.asarray([3.0, 1.0]),
                                        server_lr=0.5)
        np.testing.assert_allclose(np.asarray(out2["w"]), 0.5 * 1.5, atol=1e-6)

    def test_buffered_aggregator_downweights_stale(self):
        g = {"w": jnp.zeros(2)}
        from repro.fed.engine import CohortUpdates
        cohort = CohortUpdates(
            mean_loss=np.zeros(2), update_sqnorm=np.zeros(2),
            delta_list=[{"w": jnp.ones(2)}, {"w": jnp.full(2, -1.0)}],
            staleness=np.array([0.0, 3.0], np.float32))
        out = BufferedAggregator(staleness_power=0.5).reduce(g, cohort)
        # w̄ = [1, 0.5] / 1.5 → 2/3 · 1 + 1/3 · (−1) = 1/3
        np.testing.assert_allclose(np.asarray(out["w"]), 1.0 / 3.0, atol=1e-6)

    def test_fedavg_degeneration_on_param_cohort(self):
        """Sync-engine (param-form) cohorts: fedbuff ≡ FedAvg at η_s = 1."""
        from repro.fed.engine import CohortUpdates
        trees = [{"w": jnp.full(3, float(i))} for i in range(4)]
        g = {"w": jnp.full(3, 10.0)}
        cohort = CohortUpdates(mean_loss=np.zeros(4), update_sqnorm=np.zeros(4),
                               param_list=trees)
        out = BufferedAggregator().reduce(g, cohort)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(fs.fedavg(trees)["w"]), atol=1e-6)


class TestAsyncStalenessScoring:
    def test_override_matches_round_counter(self):
        cfg = HeteRoScoreConfig()
        state = init_client_state(5, jnp.zeros(5, jnp.float32))
        state = dataclasses.replace(
            state, last_selected=jnp.asarray([0, 3, -(10 ** 6), 7, 7], jnp.int32))
        t = jnp.int32(9)
        natural = staleness_factor(state, t, cfg)
        override = state_staleness(state, t).astype(jnp.float32)
        np.testing.assert_allclose(
            np.asarray(staleness_factor(state, t, cfg, override)),
            np.asarray(natural))
        s1 = compute_scores(state, t, cfg)
        s2 = compute_scores(state, t, cfg, staleness_override=override)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2))

    def test_override_changes_freshness(self):
        cfg = HeteRoScoreConfig()
        state = init_client_state(3, jnp.zeros(3, jnp.float32))
        base = staleness_factor(state, jnp.int32(0), cfg,
                                jnp.asarray([0.0, 2.0, 50.0]))
        assert float(base[0]) == pytest.approx(1.0)
        assert float(base[1]) == pytest.approx(1.0 + cfg.gamma * np.log1p(2.0))
        # clipped at T_max
        assert float(base[2]) == pytest.approx(
            1.0 + cfg.gamma * np.log1p(cfg.t_max))

    def test_async_selector_factory(self):
        sel_cfg = SelectorConfig(num_selected=2)
        with pytest.raises(ValueError, match="unknown selector"):
            make_async_selector("nope", sel_cfg)
        state = init_client_state(6, jnp.zeros(6, jnp.float32))
        stale = jnp.arange(6, dtype=jnp.float32)
        for name in ("heterosel", "heterosel_mult", "heterosel_pallas",
                     "oort", "random", "power_of_choice"):
            sel = make_async_selector(name, sel_cfg)
            mask, probs = sel(jax.random.PRNGKey(0), state, jnp.int32(1), stale)
            assert np.asarray(mask).sum() >= 1

    def test_pallas_async_selector_matches_jnp(self):
        """Fused async selector == jnp async selector for the same key: the
        clock-staleness override rides the kernel's ninth stacked row, and
        the in-kernel Gumbel-top-m draws the same noise as sample_clients."""
        sel_cfg = SelectorConfig(num_selected=3)
        k = 40
        state = init_client_state(
            k, jax.random.uniform(jax.random.PRNGKey(0), (k,)))
        state = dataclasses.replace(
            state,
            loss_prev=jax.random.uniform(jax.random.PRNGKey(1), (k,),
                                         minval=0.5, maxval=3.0),
            has_loss=jnp.ones(k, jnp.float32))
        stale = jax.random.uniform(jax.random.PRNGKey(2), (k,), maxval=30.0)
        ref = make_async_selector("heterosel", sel_cfg)
        fused = make_async_selector("heterosel_pallas", sel_cfg)
        m1, p1 = ref(jax.random.PRNGKey(3), state, jnp.int32(5), stale)
        m2, p2 = fused(jax.random.PRNGKey(3), state, jnp.int32(5), stale)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
        np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=2e-6)


class ArrivalStats(RoundHook):
    """Collects the async RoundContext extras the engine exposes."""

    def __init__(self):
        self.arrivals, self.stragglers, self.sim_times, self.dispatched = \
            [], [], [], []

    def on_round_end(self, ctx):
        self.arrivals.append(ctx.num_arrivals)
        self.stragglers.append(ctx.num_stragglers)
        self.sim_times.append(ctx.sim_time)
        self.dispatched.append(int(np.asarray(ctx.mask).sum()))


class TestSyncAsyncEquivalence:
    def test_equal_latencies_infinite_deadline(self, async_quickstart_setup):
        """Acceptance: quickstart-scale async == sync ±1% at equal latencies.

        With uniform latencies, deadline=∞ and ε=0 every dispatch cohort
        lands in its own round, the clock-staleness equals the round counter
        exactly, and the selector replays the synchronous draw stream — the
        selection histories are identical, and aggregation differs only by
        the delta-form float reassociation.
        """
        fed, data, model = async_quickstart_setup
        sync = FederatedSpec(model, fed, data, selector="heterosel",
                             steps_per_round=2).build().run()
        eng = FederatedSpec(model, fed, data, selector="heterosel",
                            steps_per_round=2, round_policy="async").build()
        assert isinstance(eng, AsyncFederatedEngine)
        res = eng.run()
        np.testing.assert_array_equal(res.selected_history,
                                      sync.selected_history)
        np.testing.assert_allclose(res.accuracy, sync.accuracy, atol=0.011)
        assert abs(res.final_acc - sync.final_acc) <= 0.01
        # conv-family lowering amplifies the delta-form aggregation's ulp
        # differences across SGD steps (docs/engine.md §3) — same
        # ~1e-2 envelope as the batched-vs-sequential contract
        np.testing.assert_allclose(res.train_loss, sync.train_loss, atol=2e-2)
        # every round costs exactly the (uniform) latency; zero staleness
        np.testing.assert_allclose(res.wall_clock,
                                   np.arange(1, fed.rounds + 1, dtype=float))
        np.testing.assert_array_equal(res.round_staleness,
                                      np.zeros(fed.rounds))

    def test_sync_results_carry_no_wall_clock(self, async_small_setup):
        fed, data, model = async_small_setup
        res = FederatedSpec(model, fed, data, selector="heterosel",
                            steps_per_round=2).build().run()
        assert res.wall_clock is None and res.round_staleness is None

    def test_pallas_selector_history_matches_jnp_async(self, async_small_setup):
        """selector='heterosel_pallas' on the async engine: identical
        selection history to the jnp selector (fused kernel in interpret
        mode on CPU), with real stragglers in the mix."""
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, rounds=3)
        mult = np.ones(fed.num_clients)
        mult[0] = 3.0
        acfg = AsyncConfig(deadline=1.5, over_select_frac=0.5)
        res_j = FederatedSpec(model, fed, data, selector="heterosel",
                              steps_per_round=1, round_policy="async",
                              system=mult, async_cfg=acfg).build().run()
        res_p = FederatedSpec(model, fed, data, selector="heterosel_pallas",
                              steps_per_round=1, round_policy="async",
                              system=mult, async_cfg=acfg).build().run()
        np.testing.assert_array_equal(res_p.selected_history,
                                      res_j.selected_history)
        np.testing.assert_allclose(res_p.accuracy, res_j.accuracy, atol=1e-6)


class TestDeadlineAndStragglers:
    def test_straggler_carries_forward_as_stale_arrival(self, async_small_setup):
        """10× straggler + finite deadline: its update misses the dispatch
        round, stays in flight (never re-dispatched), and aggregates later
        with staleness ≥ 1 — conservation: nothing is silently dropped."""
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, rounds=6)
        mult = np.ones(fed.num_clients)
        mult[0] = 5.0
        stats = ArrivalStats()
        eng = FederatedSpec(
            model, fed, data, selector="heterosel", steps_per_round=1,
            round_policy="async", system=mult, hooks=[stats],
            async_cfg=AsyncConfig(deadline=1.5, over_select_frac=1.0),
        ).build()
        res = eng.run()
        assert eng.stragglers_carried >= 1
        assert sum(stats.stragglers) >= 1
        assert max(res.round_staleness) > 0.0  # stale arrival was aggregated
        # conservation: dispatched == aggregated + still in flight (none dropped)
        dispatched = int(res.selected_history.sum())
        aggregated = int(sum(stats.arrivals))
        assert eng.updates_dropped == 0
        assert len(eng.clock) == int(eng._in_flight.sum())
        assert dispatched == aggregated + int(eng._in_flight.sum())
        assert np.isfinite(res.accuracy).all()
        # deadline-paced: round closes never before dispatch+deadline spacing
        assert res.wall_clock[-1] < fed.rounds * 5.0  # ≪ straggler-paced sync

    def test_over_selection_dispatches_m_over(self, async_small_setup):
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, rounds=2)
        eng = FederatedSpec(
            model, fed, data, selector="heterosel", steps_per_round=1,
            round_policy="async",
            async_cfg=AsyncConfig(over_select_frac=0.5),
        ).build()
        m_over = math.ceil(fed.num_selected * 1.5)
        assert eng.m_over == min(fed.num_clients, m_over)
        res = eng.run()
        assert res.selected_history[0].sum() == eng.m_over

    def test_max_staleness_drops_ancient_updates(self, async_small_setup):
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, rounds=5)
        mult = np.ones(fed.num_clients)
        mult[0] = 4.0
        eng = FederatedSpec(
            model, fed, data, selector="heterosel", steps_per_round=1,
            round_policy="async", system=mult,
            async_cfg=AsyncConfig(deadline=1.0, over_select_frac=1.0,
                                  max_staleness=0),
        ).build()
        eng.run()
        assert eng.updates_dropped >= 1

    def test_min_updates_counts_post_filter_arrivals(self, async_small_setup):
        """A staleness-dropped arrival must not satisfy min_updates: the
        round keeps extending until an aggregatable update exists, so no
        round ever aggregates nothing while updates are still pending."""
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, rounds=5)
        mult = np.full(fed.num_clients, 4.0)  # everyone misses the deadline
        stats = ArrivalStats()
        eng = FederatedSpec(
            model, fed, data, selector="heterosel", steps_per_round=1,
            round_policy="async", system=mult, hooks=[stats],
            async_cfg=AsyncConfig(deadline=1.0, over_select_frac=0.0,
                                  max_staleness=10),
        ).build()
        eng.run()
        assert min(stats.arrivals) >= 1

    def test_sequential_executor_async(self, async_small_setup):
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, rounds=2)
        res = FederatedSpec(model, fed, data, selector="heterosel",
                            steps_per_round=1, executor="sequential",
                            round_policy="async").build().run()
        assert np.isfinite(res.accuracy).all()
        assert len(res.wall_clock) == fed.rounds

    def test_availability_composes_with_async(self, async_small_setup):
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, rounds=3)
        avail = np.ones((fed.rounds, fed.num_clients), bool)
        avail[:, 1] = False
        res = FederatedSpec(model, fed, data, selector="heterosel",
                            steps_per_round=1, round_policy="async",
                            availability=avail).build().run()
        assert res.selected_history[:, 1].sum() == 0


class TestAsyncConfigAndCompat:
    def test_bad_config_raises(self):
        with pytest.raises(ValueError, match="deadline"):
            AsyncConfig(deadline=0.0)
        with pytest.raises(ValueError, match="over_select"):
            AsyncConfig(over_select_frac=-0.1)
        with pytest.raises(ValueError, match="base_latency"):
            AsyncConfig(base_latency=0.0)

    def test_unknown_round_policy_raises(self, async_small_setup):
        fed, data, model = async_small_setup
        with pytest.raises(ValueError, match="round_policy"):
            FederatedSpec(model, fed, data, round_policy="semi").build()

    def test_async_knobs_with_sync_policy_raise(self, async_small_setup):
        """system/async_cfg must not be silently ignored by the sync engine."""
        fed, data, model = async_small_setup
        with pytest.raises(ValueError, match="round_policy='async'"):
            FederatedSpec(model, fed, data,
                          system=np.ones(fed.num_clients)).build()
        with pytest.raises(ValueError, match="round_policy='async'"):
            FederatedSpec(model, fed, data, async_cfg=AsyncConfig()).build()

    def test_non_delta_aggregator_raises(self, async_small_setup):
        fed, data, model = async_small_setup
        with pytest.raises(ValueError, match="supports_deltas"):
            FederatedSpec(model, fed, data, round_policy="async",
                          aggregator="fedavgm").build()

    def test_chunked_batched_raises(self, async_small_setup):
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, client_chunk=2)
        with pytest.raises(ExecutorCompatError, match="client_chunk"):
            FederatedSpec(model, fed, data, round_policy="async").build()

    def test_bad_system_shape_raises(self, async_small_setup):
        fed, data, model = async_small_setup
        with pytest.raises(ValueError, match="multipliers"):
            FederatedSpec(model, fed, data, round_policy="async",
                          system=np.ones(3)).build()

    def test_checkpointing_supported(self, async_small_setup, tmp_path):
        """Async runs checkpoint: the snapshot carries the engine kind, the
        clock state and the in-flight vector (full kill/resume bitwise
        equality is pinned by tests/test_resume_matrix.py)."""
        from repro.ckpt import latest_federated_round, read_federated_meta
        from repro.fed import CheckpointHook

        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, rounds=2)
        mult = np.ones(fed.num_clients)
        mult[0] = 5.0
        eng = FederatedSpec(
            model, fed, data, selector="heterosel", steps_per_round=1,
            round_policy="async", system=mult,
            async_cfg=AsyncConfig(deadline=1.5, over_select_frac=1.0),
            hooks=[CheckpointHook(str(tmp_path), every=1)]).build()
        assert eng.snapshot_kind == "async/flat"
        eng.run()
        assert latest_federated_round(str(tmp_path)) == fed.rounds
        meta = read_federated_meta(str(tmp_path))
        assert meta["engine"] == "async/flat"
        assert meta["extra"]["clock"]["now"] > 0.0
        # every pending clock event persisted its payload delta tree
        pending = {str(e["seq"]) for e in meta["extra"]["clock"]["events"]}
        assert pending == set(meta["extra"]["pending"])

    def test_fedconfig_one_field_switch(self, async_small_setup):
        """The one-config-field mode switch the issue asks for."""
        fed, data, model = async_small_setup
        fed = dataclasses.replace(fed, round_policy="async", rounds=2)
        eng = FederatedSpec(model, fed, data, selector="heterosel",
                            steps_per_round=1).build()
        assert isinstance(eng, AsyncFederatedEngine)
        res = eng.run()
        assert res.wall_clock is not None
