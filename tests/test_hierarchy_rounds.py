"""Hierarchical two-tier federation tests (ISSUE 5 tentpole), continued.

Covers the acceptance criteria:
  * hierarchical runs under BOTH round policies ('sync' and 'async'),
    straggler edges carrying forward as stale cloud arrivals;
  * bad configurations fail loudly.

Partition and budget invariants, the E=1 flat equivalence, availability and
adaptive budgets are in ``test_hierarchy.py``.
"""

import dataclasses
import math

import numpy as np
import pytest

from repro.fed import AsyncConfig, FederatedSpec, HierarchyConfig
from repro.fed.partition import partition_edges


# ---------------------------------------------------------------------------
# Hierarchical rounds under both policies
# ---------------------------------------------------------------------------


class TestHierarchicalRounds:

    def test_sync_multi_edge(self, quickstart_setup):
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3)
        res = FederatedSpec(model, hfed, data, selector="heterosel",
                            steps_per_round=4).build().run()
        assert res.accuracy.shape == (fed.rounds,)
        # every round ships one aggregate per active edge, and the per-round
        # cohort respects the summed edge budgets (= m here)
        np.testing.assert_array_equal(res.cloud_uploads,
                                      np.full(fed.rounds, 3, np.int64))
        assert np.all(res.selected_history.sum(axis=1) == hfed.num_selected)
        # selections respect edge budgets: within each edge, each round picks
        # exactly that edge's budget
        from repro.fed.hierarchy import edge_budgets as eb
        from repro.fed.partition import partition_edges as pe
        part = pe(np.asarray(data.label_js), 3, seed=hfed.seed)
        budgets = eb(hfed.num_selected, part.sizes)
        for e in range(3):
            per_round = res.selected_history[:, part.members(e)].sum(axis=1)
            assert np.all(per_round == budgets[e])

    def test_pallas_selector_history_matches_jnp(self, quickstart_setup):
        """selector='heterosel_pallas' scores every edge in one segmented
        kernel launch (interpret mode on CPU); per-edge Gumbel sampling
        keeps the jnp path's keys and probability vectors, so the selection
        history matches selector='heterosel' exactly."""
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3,
                                   rounds=4)
        res_j = FederatedSpec(model, hfed, data, selector="heterosel",
                              steps_per_round=1).build().run()
        res_p = FederatedSpec(model, hfed, data, selector="heterosel_pallas",
                              steps_per_round=1).build().run()
        np.testing.assert_array_equal(res_p.selected_history,
                                      res_j.selected_history)
        np.testing.assert_allclose(res_p.accuracy, res_j.accuracy, atol=1e-6)
        # per-phase round timing rides along in either mode
        for r in (res_j, res_p):
            assert r.select_ms.shape == (4,)
            assert np.all(r.select_ms >= 0)
            assert np.all(r.execute_ms > 0)

    def test_outer_edge_selection_budget(self, quickstart_setup):
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3)
        res = FederatedSpec(model, hfed, data, selector="heterosel",
                            steps_per_round=4,
                            hier_cfg=HierarchyConfig(edges_per_round=2),
                            ).build().run()
        np.testing.assert_array_equal(res.cloud_uploads,
                                      np.full(fed.rounds, 2, np.int64))

    def test_async_straggler_edge_carries_forward(self, quickstart_setup):
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3,
                                   rounds=8)
        # make exactly edge 0 the straggler edge (its latency is the max over
        # its members, so the slow set must align with the partition)
        part = partition_edges(np.asarray(data.label_js), 3, seed=hfed.seed)
        mult = np.ones(hfed.num_clients)
        mult[part.members(0)] = 10.0
        res = FederatedSpec(model, hfed, data, selector="heterosel",
                            steps_per_round=4, round_policy="async",
                            system=mult,
                            async_cfg=AsyncConfig(deadline=1.5),
                            ).build().run()
        assert res.wall_clock is not None
        assert np.all(np.diff(res.wall_clock) > 0)  # clock moves forward
        # a 10× straggler edge must miss the 1.5-unit deadline and arrive
        # later as a stale cloud aggregate at least once
        assert float(np.max(res.round_staleness)) > 0.0
        # conservation: every dispatched edge aggregate eventually arrives
        # or stays pending — never silently dropped
        assert int(np.asarray(res.cloud_uploads).sum()) >= 1

    def test_async_over_selection_dispatches_extra_edges(self, quickstart_setup):
        """AsyncConfig.over_select_frac applies at the edge tier: with an
        outer budget of 2 and ε=0.5, ⌈2·1.5⌉=3 edges dispatch per round."""
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=4,
                                   rounds=3)
        res = FederatedSpec(model, hfed, data, selector="heterosel",
                            steps_per_round=1, round_policy="async",
                            hier_cfg=HierarchyConfig(edges_per_round=2),
                            async_cfg=AsyncConfig(deadline=math.inf,
                                                  over_select_frac=0.5),
                            ).build().run()
        # equal latencies + ∞ deadline ⇒ every dispatched edge arrives in
        # its own round, so uploads/round == dispatched edges/round == 3
        np.testing.assert_array_equal(res.cloud_uploads,
                                      np.full(3, 3, np.int64))

    def test_async_equal_latency_inf_deadline_is_barrier(self, quickstart_setup):
        """Homogeneous fleet + ∞ deadline: every edge arrives in its own
        round, zero staleness — async hierarchy degenerates to sync."""
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3)
        res = FederatedSpec(model, hfed, data, selector="heterosel",
                            steps_per_round=4, round_policy="async",
                            async_cfg=AsyncConfig(deadline=math.inf),
                            ).build().run()
        np.testing.assert_array_equal(res.round_staleness,
                                      np.zeros(fed.rounds))
        np.testing.assert_array_equal(res.cloud_uploads,
                                      np.full(fed.rounds, 3, np.int64))


# ---------------------------------------------------------------------------
# Loud failures
# ---------------------------------------------------------------------------


class TestLoudFailures:

    def test_missing_edge_count(self, quickstart_setup):
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical")
        with pytest.raises(ValueError, match="edge_count"):
            FederatedSpec(model, hfed, data).build()

    def test_unknown_topology(self, quickstart_setup):
        fed, data, model = quickstart_setup
        with pytest.raises(ValueError, match="topology"):
            FederatedSpec(model, fed, data, topology="mesh").build()

    def test_edge_fields_without_hierarchy(self, quickstart_setup):
        """edge_count set but topology left flat must not silently run a
        flat federation that looks two-tier."""
        fed, data, model = quickstart_setup
        bad = dataclasses.replace(fed, edge_count=4)
        with pytest.raises(ValueError, match="edge_count"):
            FederatedSpec(model, bad, data).build()
        bad = dataclasses.replace(fed, edge_budget=2)
        with pytest.raises(ValueError, match="edge_budget|edge_count"):
            FederatedSpec(model, bad, data).build()

    def test_hier_cfg_without_hierarchy(self, quickstart_setup):
        fed, data, model = quickstart_setup
        with pytest.raises(ValueError, match="hier_cfg"):
            FederatedSpec(model, fed, data, hier_cfg=HierarchyConfig()).build()

    def test_greedy_selector_with_outer_stage_refused(self, quickstart_setup):
        """oort/power_of_choice have no edge-level analogue — outer sampling
        must not silently fall back to HeteRo-biased edge choice."""
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3)
        with pytest.raises(ValueError, match="edge-level analogue"):
            FederatedSpec(model, hfed, data, selector="oort",
                          hier_cfg=HierarchyConfig(edges_per_round=2)).build()
        # without outer sampling the greedy selectors run fine (inner only)
        FederatedSpec(model, hfed, data, selector="oort").build()

    def test_random_selector_uniform_outer_stage(self, quickstart_setup):
        """selector='random' keeps the edge choice uniform as well."""
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3,
                                   rounds=3)
        res = FederatedSpec(model, hfed, data, selector="random",
                            steps_per_round=1,
                            hier_cfg=HierarchyConfig(edges_per_round=2),
                            ).build().run()
        np.testing.assert_array_equal(res.cloud_uploads,
                                      np.full(3, 2, np.int64))

    def test_incompatible_aggregator(self, quickstart_setup):
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=2)
        with pytest.raises(ValueError, match="aggregator"):
            FederatedSpec(model, hfed, data, aggregator="fedavgm").build()

    def test_checkpoint_hook_supported(self, quickstart_setup, tmp_path):
        """Hierarchical runs checkpoint: the snapshot stamps the topology in
        its engine kind and records edge_count for the resume sanity check
        (kill/resume bitwise equality: tests/test_resume_matrix.py)."""
        from repro.ckpt import latest_federated_round, read_federated_meta
        from repro.fed import CheckpointHook
        fed, data, model = quickstart_setup
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=2,
                                   rounds=2)
        spec = FederatedSpec(model, hfed, data, steps_per_round=1,
                             hooks=[CheckpointHook(str(tmp_path), every=1)])
        eng = spec.build()
        assert eng.snapshot_kind == "sync/hierarchical"
        eng.run()
        assert latest_federated_round(str(tmp_path)) == hfed.rounds
        meta = read_federated_meta(str(tmp_path))
        assert meta["engine"] == "sync/hierarchical"
        assert meta["extra"]["edge_count"] == 2
