"""Composable round-engine API tests (ISSUE 3 tentpole).

Covers the acceptance criteria:
  * golden equivalence — ``run_federated`` (now a thin wrapper over
    ``fed.engine``) reproduces, bit-for-bit on selection and to float
    tolerance on metrics, the pre-refactor monolith's results on the
    quickstart config (goldens captured at cf1971b, both execution modes);
  * kill-and-resume via ``CheckpointHook`` matches an uninterrupted run.

Aggregator parity, compression composition, hooks and metric naming are in
``test_engine_composition.py``.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax

from conftest import shared_run
from repro.fed import (
    CheckpointHook,
    FederatedSpec,
    KillAtRound,
    SimulatedPreemption,
    run_federated,
)

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden", "quickstart_metrics.json")


class TestGoldenEquivalence:
    """Acceptance: run_federated(...) keeps its signature and produces
    numerically identical metrics to the pre-refactor monolith."""

    @pytest.mark.parametrize("mode", ["batched", "sequential"])
    def test_matches_pre_refactor_golden(self, quickstart_setup, mode):
        with open(GOLDEN) as f:
            gold = json.load(f)[mode]
        fed, data, model = quickstart_setup
        res = shared_run(run_federated, model, fed, data, selector="heterosel",
                         steps_per_round=4, client_execution=mode)
        np.testing.assert_array_equal(
            np.asarray(res.selected_history).astype(int),
            np.asarray(gold["selected_history"]))
        np.testing.assert_array_equal(
            np.asarray(res.selection_counts).astype(int),
            np.asarray(gold["selection_counts"]))
        np.testing.assert_allclose(res.accuracy, gold["accuracy"], atol=1e-6)
        np.testing.assert_allclose(res.train_loss, gold["train_loss"], atol=1e-6)

    def test_spec_api_equals_wrapper_exactly(self, quickstart_setup):
        fed, data, model = quickstart_setup
        rw = shared_run(run_federated, model, fed, data, selector="heterosel",
                        steps_per_round=4, client_execution="batched")
        rs = FederatedSpec(model, fed, data, selector="heterosel",
                           steps_per_round=4, executor="batched").build().run()
        np.testing.assert_array_equal(rw.selected_history, rs.selected_history)
        np.testing.assert_array_equal(rw.accuracy, rs.accuracy)
        np.testing.assert_array_equal(rw.train_loss, rs.train_loss)


class TestCheckpointResume:
    """Acceptance: a run killed at round t and resumed via CheckpointHook
    matches an uninterrupted run."""

    @pytest.mark.parametrize("aggregator", ["fedavg", "fedavgm"])
    def test_kill_and_resume_matches_uninterrupted(self, tmp_path, small_setup,
                                                   aggregator):
        fed, data, model = small_setup
        fed = dataclasses.replace(fed, rounds=5)
        full = FederatedSpec(model, fed, data, selector="heterosel",
                             steps_per_round=2,
                             aggregator=aggregator).build().run()

        ckdir = str(tmp_path / aggregator)
        killed_fed = dataclasses.replace(fed, rounds=3)  # "killed" at round 3
        FederatedSpec(model, killed_fed, data, selector="heterosel",
                      steps_per_round=2, aggregator=aggregator,
                      hooks=[CheckpointHook(ckdir, every=1)]).build().run()

        resumed_engine = FederatedSpec(
            model, fed, data, selector="heterosel", steps_per_round=2,
            aggregator=aggregator,
            hooks=[CheckpointHook(ckdir, every=1, resume=True)]).build()
        resumed = resumed_engine.run()

        assert resumed_engine.start_round == 3
        np.testing.assert_array_equal(resumed.selected_history,
                                      full.selected_history)
        np.testing.assert_allclose(resumed.accuracy, full.accuracy, atol=1e-6)
        np.testing.assert_allclose(resumed.train_loss, full.train_loss, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(full.params),
                        jax.tree_util.tree_leaves(resumed.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_resume_with_adaptive_mu_matches(self, tmp_path, small_setup):
        """Hook state (the μ controller's EMAs + history) is checkpointed,
        so adaptive-μ runs also resume exactly, with the full μ trace.

        The kill must happen mid-flight of the *same* rounds=5 config (not a
        shortened rounds=3 run): the μ controller's horizon term
        ``rounds - t`` differs otherwise."""
        fed, data, model = small_setup
        fed = dataclasses.replace(fed, rounds=5)
        full = FederatedSpec(model, fed, data, selector="heterosel",
                             steps_per_round=2,
                             hooks=["adaptive_mu"]).build().run()

        ckdir = str(tmp_path / "amu")
        with pytest.raises(SimulatedPreemption):
            # checkpoint hook precedes the kill switch → round 3 is on disk
            FederatedSpec(model, fed, data, selector="heterosel",
                          steps_per_round=2,
                          hooks=["adaptive_mu", CheckpointHook(ckdir, every=1),
                                 KillAtRound(2)]).build().run()
        resumed = FederatedSpec(model, fed, data, selector="heterosel",
                                steps_per_round=2,
                                hooks=["adaptive_mu",
                                       CheckpointHook(ckdir)]).build().run()
        assert len(resumed.mu_history) == fed.rounds
        np.testing.assert_allclose(resumed.mu_history, full.mu_history)
        np.testing.assert_allclose(resumed.accuracy, full.accuracy, atol=1e-6)
        np.testing.assert_array_equal(resumed.selected_history,
                                      full.selected_history)

    def test_fresh_dir_runs_from_scratch(self, tmp_path, small_setup):
        fed, data, model = small_setup
        res = FederatedSpec(
            model, fed, data, selector="heterosel", steps_per_round=2,
            hooks=[CheckpointHook(str(tmp_path / "fresh"), every=2)],
        ).build().run()
        assert len(res.accuracy) == fed.rounds
        from repro.ckpt import latest_federated_round
        assert latest_federated_round(str(tmp_path / "fresh")) == fed.rounds
