"""Kill-at-round-t golden resume matrix (ISSUE 7 tentpole acceptance).

Every ``round_policy × topology`` combination — {sync, async} × {flat,
hierarchical} — is run three times via the ``preempt_harness`` fixture:
uninterrupted, killed by a ``SimulatedPreemption`` after round t (with a
``CheckpointHook`` saving first), and resumed from the checkpoint
directory. The resumed run must reproduce the uninterrupted run
**bitwise**: metrics, selection history, ``wall_clock`` /
``round_staleness`` traces, ``cloud_uploads``, final params, and the
state-layout dtypes. This file holds the f32-state half of the matrix;
the ``compact_state`` half and the selector-zoo cells are in
``test_resume_matrix_variants.py``.

The async configurations are deliberately hostile (``make_spec_factory``
in conftest.py), so at the kill point the virtual clock genuinely holds
in-flight completions (pending delta payloads, busy clients/edges) that the
snapshot must carry.

Also covered here: the mid-phase kill variant, engine-kind and edge-count
mismatch refusal, compact_state flips refused on the dtype schema,
``keep_last`` retention through a real engine, and the corrupt-latest
fallback (loud, never silent).
"""

import dataclasses
import os

import numpy as np
import pytest

from conftest import (
    KILL_AT,
    MATRIX,
    assert_bitwise_resume,
    make_spec_factory,
    shared_run,
)
from repro.ckpt import (
    CheckpointMismatchError,
    list_federated_rounds,
    read_federated_meta,
)
from repro.fed import (
    CheckpointHook,
    FederatedSpec,
    KillAtRound,
    SimulatedPreemption,
)


# compact=True is in test_resume_matrix_variants.py; the ids stay the ones
# that CI pins
@pytest.mark.parametrize("policy,topology", MATRIX)
@pytest.mark.parametrize("compact", [False], ids=["f32state"])
def test_kill_at_round_t_resumes_bitwise(matrix_setup, preempt_harness,
                                         policy, topology, compact):
    make_spec = make_spec_factory(matrix_setup, policy, topology, compact)
    full, resumed, engine = preempt_harness(make_spec([]), KILL_AT)
    assert_bitwise_resume(full, resumed, engine, compact=compact)


def test_async_snapshot_carries_in_flight_state(matrix_setup,
                                                preempt_harness):
    """The hostile async profile must actually exercise the clock payload
    path — otherwise the matrix would pass with an empty event queue."""
    make_spec = make_spec_factory(matrix_setup, "async", "flat", False)
    full, resumed, engine = preempt_harness(make_spec([]), KILL_AT)
    meta_rounds = list_federated_rounds(engine.hooks[-1].path)
    assert meta_rounds  # checkpoints were written
    metas = [read_federated_meta(engine.hooks[-1].path, r)
             for r in meta_rounds]
    assert any(m["extra"]["clock"]["events"] for m in metas), (
        "no snapshot ever held an in-flight completion; the async matrix "
        "config is not hostile enough to prove payload persistence")
    assert_bitwise_resume(full, resumed, engine, compact=False)


def test_mid_phase_kill_resumes_bitwise(matrix_setup, preempt_harness):
    """phase='round_start' dies at the start of round t+1 — after the
    round-t snapshot but inside the next round's hook sequence."""
    make_spec = make_spec_factory(matrix_setup, "async", "hierarchical",
                                  False)
    full, resumed, engine = preempt_harness(make_spec([]), KILL_AT,
                                            phase="round_start")
    assert_bitwise_resume(full, resumed, engine, compact=False)


class TestMismatchRefusal:
    def test_engine_kind_mismatch_is_loud(self, matrix_setup, tmp_path):
        make_sync = make_spec_factory(matrix_setup, "sync", "flat", False)
        ckdir = str(tmp_path / "kind")
        with pytest.raises(SimulatedPreemption):
            make_sync([CheckpointHook(ckdir, every=1),
                       KillAtRound(KILL_AT)]).build().run()
        make_async = make_spec_factory(matrix_setup, "async", "flat", False)
        with pytest.raises(CheckpointMismatchError, match="sync/flat"):
            make_async([CheckpointHook(ckdir, every=1)]).build().run()

    def test_compact_state_flip_is_loud(self, matrix_setup, tmp_path):
        make_compact = make_spec_factory(matrix_setup, "sync", "flat", True)
        ckdir = str(tmp_path / "compact")
        with pytest.raises(SimulatedPreemption):
            make_compact([CheckpointHook(ckdir, every=1),
                          KillAtRound(KILL_AT)]).build().run()
        make_f32 = make_spec_factory(matrix_setup, "sync", "flat", False)
        with pytest.raises(CheckpointMismatchError, match="dtype"):
            make_f32([CheckpointHook(ckdir, every=1)]).build().run()

    def test_edge_count_mismatch_is_loud(self, matrix_setup, tmp_path):
        fed, data, model = matrix_setup
        ckdir = str(tmp_path / "edges")
        hfed = dataclasses.replace(fed, topology="hierarchical", edge_count=3)
        with pytest.raises(SimulatedPreemption):
            FederatedSpec(model, hfed, data, selector="heterosel",
                          steps_per_round=2,
                          hooks=[CheckpointHook(ckdir, every=1),
                                 KillAtRound(KILL_AT)]).build().run()
        hfed2 = dataclasses.replace(hfed, edge_count=2)
        with pytest.raises(CheckpointMismatchError, match="edge_count"):
            FederatedSpec(model, hfed2, data, selector="heterosel",
                          steps_per_round=2,
                          hooks=[CheckpointHook(ckdir, every=1)]
                          ).build().run()


class TestRetentionAndFallback:
    def test_keep_last_retains_exactly_n_and_resumes_latest(
            self, matrix_setup, tmp_path):
        make_spec = make_spec_factory(matrix_setup, "sync", "flat", False)
        full = shared_run(make_spec([]))
        ckdir = str(tmp_path / "keep")
        with pytest.raises(SimulatedPreemption):
            make_spec([CheckpointHook(ckdir, every=1, keep_last=2),
                       KillAtRound(2)]).build().run()
        assert list_federated_rounds(ckdir) == [2, 3]  # exactly N remain
        engine = make_spec([CheckpointHook(ckdir, every=1,
                                           keep_last=2)]).build()
        resumed = engine.run()
        assert engine.start_round == 3  # picked the latest snapshot
        np.testing.assert_array_equal(resumed.selected_history,
                                      full.selected_history)
        np.testing.assert_array_equal(np.asarray(resumed.accuracy),
                                      np.asarray(full.accuracy))

    def test_corrupt_latest_falls_back_loudly(self, matrix_setup, tmp_path):
        make_spec = make_spec_factory(matrix_setup, "sync", "flat", False)
        full = shared_run(make_spec([]))
        ckdir = str(tmp_path / "corrupt")
        with pytest.raises(SimulatedPreemption):
            make_spec([CheckpointHook(ckdir, every=1),
                       KillAtRound(2)]).build().run()
        assert list_federated_rounds(ckdir) == [1, 2, 3]
        # truncate the newest npz mid-write, like a real preemption would
        npz = os.path.join(ckdir, "fedround_00000003.npz")
        with open(npz, "r+b") as f:
            f.truncate(100)
        engine = make_spec([CheckpointHook(ckdir, every=1)]).build()
        with pytest.warns(RuntimeWarning, match="skipping unreadable"):
            resumed = engine.run()
        assert engine.start_round == 2  # fell back to the newest readable
        np.testing.assert_array_equal(resumed.selected_history,
                                      full.selected_history)
        np.testing.assert_array_equal(np.asarray(resumed.accuracy),
                                      np.asarray(full.accuracy))

    def test_all_snapshots_corrupt_raises(self, matrix_setup, tmp_path):
        make_spec = make_spec_factory(matrix_setup, "sync", "flat", False)
        ckdir = str(tmp_path / "allbad")
        with pytest.raises(SimulatedPreemption):
            make_spec([CheckpointHook(ckdir, every=1),
                       KillAtRound(1)]).build().run()
        for r in list_federated_rounds(ckdir):
            with open(os.path.join(ckdir, f"fedround_{r:08d}.npz"),
                      "r+b") as f:
                f.truncate(10)
        with pytest.raises(RuntimeError, match="no readable snapshot"):
            make_spec([CheckpointHook(ckdir, every=1)]).build().run()


def test_kill_at_round_validates_phase():
    with pytest.raises(ValueError, match="phase"):
        KillAtRound(2, phase="mid_gradient")
