"""Hierarchical two-tier federation tests (ISSUE 5 tentpole).

Covers the acceptance criteria:
  * edge-partition invariants — every client in exactly one edge, both
    partition modes, determinism;
  * budget invariants — per-edge budgets sum to ≤ the global m, never exceed
    edge sizes, E=1 degenerates to the full budget;
  * E=1 + full budget reproduces flat selection exactly (selection-identical
    on the quickstart config, metrics bitwise), with and without churn;
  * pooled edge state feeds the unchanged scoring machinery;
  * client availability and adaptive per-edge budgets under hierarchy.

Hierarchical rounds under both round policies and the loud failures of bad
configurations are in ``test_hierarchy_rounds.py``.
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.scoring import HeteRoScoreConfig, compute_scores
from repro.core.state import init_client_state, pool_client_state
from repro.fed import FederatedSpec, edge_budgets
from repro.fed.partition import EdgePartition, partition_edges


# ---------------------------------------------------------------------------
# Partition invariants
# ---------------------------------------------------------------------------


class TestEdgePartition:

    @pytest.mark.parametrize("mode", ["similarity", "random"])
    @pytest.mark.parametrize("k,e", [(12, 1), (12, 3), (12, 5), (40, 7)])
    def test_every_client_in_exactly_one_edge(self, mode, k, e):
        js = np.random.default_rng(0).random(k)
        part = partition_edges(js, e, mode=mode, seed=3)
        assert part.assignment.shape == (k,)
        # exactly one edge per client: ids valid and sizes sum to K
        assert part.assignment.min() >= 0
        assert part.assignment.max() < e
        assert part.sizes.sum() == k
        # member lists are a disjoint cover of [0, K)
        all_members = np.concatenate(part.member_lists())
        assert sorted(all_members.tolist()) == list(range(k))

    def test_sizes_balanced(self):
        part = partition_edges(np.arange(13, dtype=float), 4)
        assert part.sizes.max() - part.sizes.min() <= 1

    def test_similarity_groups_similar_skew(self):
        js = np.array([0.9, 0.1, 0.85, 0.15, 0.8, 0.2])
        part = partition_edges(js, 2, mode="similarity")
        # the three low-JS clients share an edge, the three high-JS the other
        low = part.assignment[[1, 3, 5]]
        high = part.assignment[[0, 2, 4]]
        assert len(set(low.tolist())) == 1
        assert len(set(high.tolist())) == 1
        assert low[0] != high[0]

    def test_random_mode_deterministic_per_seed(self):
        js = np.random.default_rng(1).random(30)
        a = partition_edges(js, 5, mode="random", seed=7)
        b = partition_edges(js, 5, mode="random", seed=7)
        c = partition_edges(js, 5, mode="random", seed=8)
        np.testing.assert_array_equal(a.assignment, b.assignment)
        assert not np.array_equal(a.assignment, c.assignment)

    def test_bad_configs_loud(self):
        js = np.ones(6)
        with pytest.raises(ValueError, match="edge_count"):
            partition_edges(js, 0)
        with pytest.raises(ValueError, match="edge_count"):
            partition_edges(js, 7)
        with pytest.raises(ValueError, match="mode"):
            partition_edges(js, 2, mode="kmeans")
        with pytest.raises(ValueError, match="at least one"):
            EdgePartition(assignment=np.zeros(4, np.int32), edge_count=2)


# ---------------------------------------------------------------------------
# Budget invariants
# ---------------------------------------------------------------------------


class TestEdgeBudgets:

    @pytest.mark.parametrize("m,sizes", [
        (6, [4, 4, 4]), (6, [1, 5, 6]), (5, [3, 3]), (1, [4, 4, 4]),
        (12, [4, 4, 4]), (512, [32] * 32),
    ])
    def test_sum_at_most_global_budget(self, m, sizes):
        b = edge_budgets(m, np.asarray(sizes))
        assert b.sum() <= m
        assert b.sum() == min(m, sum(sizes))  # never under-spends either
        assert np.all(b <= np.asarray(sizes))
        assert np.all(b >= 0)

    def test_e1_degenerates_to_full_budget(self):
        assert edge_budgets(6, np.asarray([12])).tolist() == [6]

    def test_proportional_to_size(self):
        b = edge_budgets(8, np.asarray([2, 6]))
        assert b.tolist() == [2, 6]

    def test_explicit_edge_budget_caps_at_size(self):
        b = edge_budgets(6, np.asarray([2, 8, 8]), edge_budget=4)
        assert b.tolist() == [2, 4, 4]


# ---------------------------------------------------------------------------
# Pooled edge state
# ---------------------------------------------------------------------------


class TestPooledState:

    def test_pooled_state_scoreable(self):
        k = 10
        state = init_client_state(k, jnp.linspace(0.0, 0.6, k))
        assignment = jnp.asarray(np.arange(k) % 3)
        pooled = pool_client_state(state, assignment, 3)
        assert pooled.num_clients == 3
        scores = compute_scores(pooled, jnp.int32(4), HeteRoScoreConfig())
        assert scores.shape == (3,)
        assert bool(jnp.all(jnp.isfinite(scores)))

    def test_observed_weighted_means(self):
        k = 4
        state = init_client_state(k, jnp.zeros(k))
        # clients 0,1 on edge 0; only client 0 observed with loss 2.0
        state = dataclasses.replace(
            state,
            loss_prev=jnp.asarray([2.0, 0.0, 3.0, 5.0]),
            has_loss=jnp.asarray([1.0, 0.0, 1.0, 1.0]),
        )
        pooled = pool_client_state(state, jnp.asarray([0, 0, 1, 1]), 2)
        # edge 0 pools only its observed member; edge 1 the mean of both
        np.testing.assert_allclose(np.asarray(pooled.loss_prev), [2.0, 4.0])
        np.testing.assert_array_equal(np.asarray(pooled.has_loss), [1.0, 1.0])


# ---------------------------------------------------------------------------
# E=1 degenerate case == flat selection (the pinned contract)
# ---------------------------------------------------------------------------


class TestFlatEquivalence:

    def test_e1_full_budget_matches_flat(self, quickstart_setup):
        fed, data, model = quickstart_setup
        flat = FederatedSpec(model, fed, data, selector="heterosel",
                             steps_per_round=4).build().run()
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=1)
        hier = FederatedSpec(model, hfed, data, selector="heterosel",
                             steps_per_round=4).build().run()
        np.testing.assert_array_equal(hier.selected_history,
                                      flat.selected_history)
        np.testing.assert_array_equal(hier.selection_counts,
                                      flat.selection_counts)
        np.testing.assert_allclose(hier.accuracy, flat.accuracy, atol=0.0)
        np.testing.assert_allclose(hier.train_loss, flat.train_loss, atol=0.0)
        # one edge aggregate reaches the cloud per round
        np.testing.assert_array_equal(hier.cloud_uploads,
                                      np.ones(fed.rounds, np.int64))


# ---------------------------------------------------------------------------
# Availability and adaptive budgets under hierarchy
# ---------------------------------------------------------------------------


class TestHierarchicalAvailability:
    """Client churn threads through the inner per-edge selection stage
    (ISSUE 10 lifted the former 'no availability under hierarchy' gap)."""

    def _trace(self, fed):
        from repro.fed.availability import AvailabilityTrace
        return AvailabilityTrace(fed.num_clients, p_stay_online=0.6,
                                 p_come_online=0.4, seed=3).masks(fed.rounds)

    def test_selected_subset_of_available(self, quickstart_setup):
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3)
        avail = self._trace(hfed)
        res = FederatedSpec(model, hfed, data, selector="heterosel",
                            steps_per_round=1,
                            availability=avail).build().run()
        assert not (res.selected_history.astype(bool) & ~avail).any()
        # short rounds are allowed (an edge may be mostly offline), never
        # empty federations: at least one round trained someone
        assert res.selected_history.sum() > 0

    def test_segmented_pallas_matches_jnp_under_churn(self, quickstart_setup):
        """The segmented-kernel path re-masks host-side with the same
        `_remask` the jnp path uses, so churned selection histories match
        exactly across selector implementations."""
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3,
                                   rounds=4)
        avail = self._trace(hfed)
        res_j = FederatedSpec(model, hfed, data, selector="heterosel",
                              steps_per_round=1,
                              availability=avail).build().run()
        res_p = FederatedSpec(model, hfed, data, selector="heterosel_pallas",
                              steps_per_round=1,
                              availability=avail).build().run()
        np.testing.assert_array_equal(res_p.selected_history,
                                      res_j.selected_history)

    def test_e1_full_budget_matches_flat_under_churn(self, quickstart_setup):
        """The E=1 flat-equivalence contract survives availability masking:
        one edge holding everyone + churn ≡ the flat engine + churn."""
        fed, data, model = quickstart_setup
        avail = self._trace(fed)
        res_flat = FederatedSpec(model, fed, data, selector="heterosel",
                                 steps_per_round=1,
                                 availability=avail).build().run()
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=1)
        res_hier = FederatedSpec(model, hfed, data, selector="heterosel",
                                 steps_per_round=1,
                                 availability=avail).build().run()
        np.testing.assert_array_equal(res_hier.selected_history,
                                      res_flat.selected_history)
        np.testing.assert_array_equal(np.asarray(res_hier.accuracy),
                                      np.asarray(res_flat.accuracy))


class TestAdaptiveBudgetsEngine:
    """selector='adaptive' under hierarchy: per-edge budgets retune online."""

    def test_budgets_retune_and_respect_invariants(self, quickstart_setup):
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3)
        engine = FederatedSpec(model, hfed, data, selector="adaptive",
                               steps_per_round=1).build()
        res = engine.run()
        budgets = np.asarray(engine.budgets)
        assert int(budgets.sum()) <= hfed.num_selected
        assert (budgets >= 0).all()
        assert (budgets <= engine.partition.sizes).all()
        # the controller actually observed utilities during the run
        assert engine._budget_ctl.utilities is not None
        assert np.all(res.selected_history.sum(axis=1)
                      <= hfed.num_selected)

    def test_adaptive_with_explicit_edge_budget_is_loud(self,
                                                        quickstart_setup):
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3,
                                   edge_budget=2)
        with pytest.raises(ValueError, match="edge_budget"):
            FederatedSpec(model, hfed, data, selector="adaptive").build()
