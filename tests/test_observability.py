"""Telemetry-plane tests (ISSUE 9 tentpole: repro.obs).

Covers the acceptance criteria:
  * trace schema — the exported ``trace.json`` is valid Chrome
    trace-event JSON: balanced ``B``/``E`` pairs, monotone timestamps per
    (pid, tid) track, non-negative ``X`` durations, both timelines (host
    wall-time pid and virtual sim-time pid) present;
  * stream fidelity — replaying the append-only ``trace.jsonl`` yields
    exactly the in-memory event list;
  * determinism — two seeded runs emit identical sim-timeline traces and
    identical wall-timeline event structure (names/order);
  * telemetry golden matrix — training metrics, selection history,
    ``wall_clock``, ``cloud_uploads`` and final params are **bitwise**
    identical with and without a ``TelemetryHook``, for every
    {sync, async} × {flat, hierarchical} cell;
  * ``StabilityReport`` reproduces the ``FLResult`` Table-1 triple
    (peak / final / stability drop) exactly, online and offline;
  * satellite 1 — resumed runs restore the pre-preemption rounds' real
    ``select_ms``/``execute_ms``/``aggregate_ms`` instead of zeros;
  * resume-safe telemetry — a killed + resumed telemetry run emits a
    seamless trace and a gap-free per-round registry;
  * ``VerboseHook`` renders from the (shared) ``MetricsRegistry`` with
    its historical output format preserved.
"""

import contextlib
import io
import json

import numpy as np
import pytest

import jax

from conftest import KILL_AT, MATRIX, ROUNDS, make_spec_factory
from repro.fed import CheckpointHook, KillAtRound, SimulatedPreemption
from repro.obs import (
    MetricsRegistry,
    StabilityReport,
    TelemetryHook,
    gini,
    load_jsonl,
    selection_entropy,
    validate_trace_events,
)
from repro.obs.trace import NULL_TRACER, PID_SIM, PID_WALL


@pytest.fixture(scope="module")
def sync_run(matrix_setup, tmp_path_factory):
    """One telemetry-attached sync/flat run shared by the cheap tests."""
    td = str(tmp_path_factory.mktemp("obs") / "run0")
    hook = TelemetryHook(trace_dir=td)
    res = make_spec_factory(matrix_setup, "sync", "flat")(
        [hook]).build().run()
    return res, hook, td


@pytest.fixture(scope="module")
def verbose_run(matrix_setup):
    """One sync/flat run with ``verbose=True`` and no other hook, and what
    it printed: ``(engine, result, stdout)``."""
    eng = make_spec_factory(matrix_setup, "sync", "flat")(
        [], verbose=True).build()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        res = eng.run()
    return eng, res, out.getvalue()


# ---------------------------------------------------------------------------
# Chrome trace-event schema
# ---------------------------------------------------------------------------


class TestTraceSchema:
    def test_export_is_valid_chrome_trace(self, sync_run):
        _, _, td = sync_run
        with open(f"{td}/trace.json") as f:
            doc = json.load(f)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        evs = doc["traceEvents"]
        validate_trace_events(evs)
        # both timelines present, with metadata naming them
        assert {e["pid"] for e in evs} == {PID_WALL, PID_SIM}
        names = {(e["pid"], e.get("args", {}).get("name"))
                 for e in evs if e["ph"] == "M" and e["name"] == "process_name"}
        assert (PID_WALL, "host wall-time") in names
        assert (PID_SIM, "virtual sim-time") in names
        # every engine phase of every round landed on the wall timeline
        walls = [e["name"] for e in evs if e["pid"] == PID_WALL
                 and e["ph"] == "B"]
        for phase in ("select", "execute", "aggregate", "eval"):
            assert walls.count(phase) == ROUNDS, phase
        sims = [e for e in evs if e["pid"] == PID_SIM and e["ph"] == "X"]
        assert [e["args"]["round"] for e in sims] == list(range(ROUNDS))

    def test_be_pairs_balanced_and_monotone(self, sync_run):
        _, hook, _ = sync_run
        depth, last_ts = {}, {}
        for e in hook.recorder.events:
            if e["ph"] == "M":
                continue
            key = (e["pid"], e["tid"])
            assert e["ts"] >= last_ts.get(key, float("-inf")), e
            last_ts[key] = e["ts"]
            if e["ph"] == "B":
                depth[key] = depth.get(key, 0) + 1
            elif e["ph"] == "E":
                depth[key] = depth[key] - 1
                assert depth[key] >= 0
        assert all(d == 0 for d in depth.values())

    def test_validator_rejects_malformed(self):
        with pytest.raises(ValueError):
            validate_trace_events([])
        ok = {"name": "a", "ph": "B", "ts": 0.0, "pid": 1, "tid": 1}
        end = {"name": "a", "ph": "E", "ts": 1.0, "pid": 1, "tid": 1}
        with pytest.raises(ValueError, match="unbalanced"):
            validate_trace_events([ok])  # B without E
        with pytest.raises(ValueError):
            validate_trace_events([ok, dict(end, name="b")])  # mis-nested
        with pytest.raises(ValueError, match="monotone"):
            validate_trace_events(
                [ok, end, dict(ok, ts=0.5), dict(end, ts=0.6)])
        with pytest.raises(ValueError, match="dur"):
            validate_trace_events(
                [{"name": "x", "ph": "X", "ts": 0.0, "dur": -1.0,
                  "pid": 2, "tid": 1}])
        with pytest.raises(ValueError):
            validate_trace_events([dict(ok, ph="Q"), end])
        with pytest.raises(ValueError, match="lacks"):
            validate_trace_events([{"ph": "B", "ts": 0.0, "pid": 1, "tid": 1}])

    def test_jsonl_replay_equals_memory(self, sync_run):
        _, hook, td = sync_run
        assert load_jsonl(f"{td}/trace.jsonl") == hook.recorder.events

    def test_seeded_runs_trace_deterministically(self, matrix_setup,
                                                 sync_run, tmp_path):
        make_spec = make_spec_factory(matrix_setup, "sync", "flat")

        def trace(d):
            hook = TelemetryHook(trace_dir=str(tmp_path / d))
            make_spec([hook]).build().run()
            return hook.recorder.events

        # the first seeded run is the module's shared one
        a, b = list(sync_run[1].recorder.events), trace("b")
        # the sim timeline is virtual time: bit-identical across runs
        sim = lambda evs: [e for e in evs if e["pid"] == PID_SIM]
        assert sim(a) == sim(b)
        # wall times differ; names/order/args structure must not
        shape = lambda evs: [(e["name"], e["ph"], e["tid"],
                              e.get("args", {}).get("round"))
                             for e in evs if e["pid"] == PID_WALL]
        assert shape(a) == shape(b)


# ---------------------------------------------------------------------------
# Telemetry golden matrix: bitwise non-perturbation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy,topology", MATRIX)
def test_telemetry_golden_matrix(matrix_setup, policy, topology):
    """Attaching the full telemetry plane must not move a single bit of
    training: metrics, selection history, wall clock, uploads, params."""
    make_spec = make_spec_factory(matrix_setup, policy, topology)
    bare_eng = make_spec([]).build()
    assert bare_eng.tracer is NULL_TRACER and not bare_eng.tracer.enabled
    bare = bare_eng.run()
    hook = TelemetryHook()  # in-memory: recorder + registry, no files
    wired = make_spec([hook]).build().run()

    np.testing.assert_array_equal(wired.selected_history,
                                  bare.selected_history)
    np.testing.assert_array_equal(np.asarray(wired.accuracy),
                                  np.asarray(bare.accuracy))
    np.testing.assert_array_equal(np.asarray(wired.train_loss),
                                  np.asarray(bare.train_loss))
    for name in ("wall_clock", "round_staleness", "cloud_uploads"):
        a, b = getattr(bare, name), getattr(wired, name)
        assert (a is None) == (b is None), name
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(bare.params),
            jax.tree_util.tree_leaves_with_path(wired.params)):
        assert ka == kb
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8),
                                      err_msg=str(ka))
    # and the side channel actually recorded a valid trace + full registry
    validate_trace_events(hook.recorder.events)
    assert [r["round"] for r in hook.registry.rounds] == list(range(ROUNDS))
    assert wired.telemetry is not None
    assert wired.telemetry["stability"]["rounds"] == ROUNDS
    names = {e["name"] for e in hook.recorder.events if e["ph"] != "M"}
    want = {"execute", "aggregate", "eval", "round"}
    if topology == "hierarchical":
        # hierarchical rounds split selection into the outer (edge) and
        # inner (per-edge client) phases and trace each edge's execution
        want |= {"outer_select", "inner_select", "edge_execute"}
    else:
        want |= {"select"}
    assert want <= names
    if policy == "async":
        assert "completion" in names  # clock events on the sim timeline


# ---------------------------------------------------------------------------
# Stability report: Table-1 triple exactness
# ---------------------------------------------------------------------------


class TestStabilityReport:
    def test_triple_matches_flresult_exactly(self, sync_run):
        res, hook, _ = sync_run
        rep = StabilityReport.from_result(res)
        s = res.summary()
        assert rep.peak == s["peak_acc"]
        assert rep.final == s["final_acc"]
        assert rep.stability_drop == s["stability_drop"]
        assert rep.stable == s["stable_acc"]
        # online path (registry) agrees with the offline path (FLResult)
        online = StabilityReport.from_registry(hook.registry, res.metric_name)
        assert online.summary() == rep.summary()
        assert online.selection_counts == rep.selection_counts

    def test_artifacts_written(self, sync_run):
        res, hook, td = sync_run
        md, js = hook.report_paths
        assert md == f"{td}/report.md" and js == f"{td}/report.json"
        with open(js) as f:
            payload = json.load(f)
        assert payload["peak"] == res.summary()["peak_acc"]
        assert payload["stability_drop"] == res.summary()["stability_drop"]
        assert len(payload["metric"]) == ROUNDS
        text = open(md).read()
        assert "| stability drop (peak − final) |" in text
        assert "## Fairness trajectory" in text
        # and the run surfaced the same numbers through FLResult.telemetry
        assert res.telemetry["stability"]["peak"] == payload["peak"]

    def test_fairness_units(self):
        assert gini([5, 5, 5, 5]) == 0.0
        assert selection_entropy([5, 5, 5, 5]) == 1.0
        n = 8
        one_hot = [0] * (n - 1) + [7]
        assert gini(one_hot) == pytest.approx((n - 1) / n)
        assert selection_entropy(one_hot) == 0.0
        assert gini([]) == 0.0 and selection_entropy([]) == 0.0
        assert gini([0, 0]) == 0.0 and selection_entropy([0, 0]) == 0.0


# ---------------------------------------------------------------------------
# Resume: real prefix timings (satellite 1) + seamless telemetry
# ---------------------------------------------------------------------------


class TestResume:
    def test_resumed_prefix_keeps_real_phase_timings(self, matrix_setup,
                                                     tmp_path):
        """Round snapshots persist select/execute/aggregate wall times
        (format v3); a resumed run reports the prefix's real numbers, not
        the zero backfill it used to."""
        make_spec = make_spec_factory(matrix_setup, "sync", "flat")
        ck = str(tmp_path / "ck")
        killed = make_spec([CheckpointHook(ck, every=1),
                            KillAtRound(KILL_AT)]).build()
        with pytest.raises(SimulatedPreemption):
            killed.run()
        prefix = [list(killed.metrics.select_ms),
                  list(killed.metrics.execute_ms),
                  list(killed.metrics.aggregate_ms)]
        assert all(len(p) == KILL_AT + 1 for p in prefix)

        res = make_spec([CheckpointHook(ck, every=1)]).build().run()
        for name, want in zip(("select_ms", "execute_ms", "aggregate_ms"),
                              prefix):
            got = np.asarray(getattr(res, name))
            assert got.shape == (ROUNDS,)
            np.testing.assert_array_equal(got[:KILL_AT + 1],
                                          np.asarray(want), err_msg=name)
            assert (got > 0.0).all(), name  # no zero backfill anywhere

    def test_killed_and_resumed_telemetry_is_seamless(self, matrix_setup,
                                                      tmp_path):
        """TelemetryHook state (registry + sim cursor) rides the checkpoint
        and the JSONL stream appends, so kill+resume yields one gap-free
        trace and a registry covering every round exactly once."""
        make_spec = make_spec_factory(matrix_setup, "async", "flat")
        td, ck = str(tmp_path / "trace"), str(tmp_path / "ck")
        with pytest.raises(SimulatedPreemption):
            make_spec([TelemetryHook(trace_dir=td),
                       CheckpointHook(ck, every=1),
                       KillAtRound(KILL_AT)]).build().run()
        hook = TelemetryHook(trace_dir=td)
        res = make_spec([hook, CheckpointHook(ck, every=1)]).build().run()

        rows = hook.registry.rounds
        assert [r["round"] for r in rows] == list(range(ROUNDS))
        assert all(r["select_ms"] > 0.0 for r in rows)  # prefix not zeroed
        # participation counts accumulated across the preemption
        np.testing.assert_array_equal(
            hook.registry.selection_counts,
            np.asarray(res.selected_history).astype(np.int64).sum(0))
        # the exported trace covers both run segments and still validates
        events = load_jsonl(f"{td}/trace.jsonl")
        validate_trace_events(events)
        sims = [e for e in events if e["pid"] == PID_SIM and e["ph"] == "X"]
        assert [e["args"]["round"] for e in sims] == list(range(ROUNDS))
        with open(f"{td}/trace.json") as f:
            assert json.load(f)["traceEvents"] == events


# ---------------------------------------------------------------------------
# VerboseHook on the registry (single source of truth)
# ---------------------------------------------------------------------------


class TestVerboseOnRegistry:
    def test_output_format_preserved(self, verbose_run):
        _, res, out = verbose_run
        acc = np.asarray(res.accuracy)
        loss = np.asarray(res.train_loss)
        assert (f"[heterosel] round   0  accuracy={acc[0]:.4f}  "
                f"loss={loss[0]:.4f}") in out
        last = ROUNDS - 1
        assert (f"[heterosel] round {last:3d}  accuracy={acc[last]:.4f}  "
                f"loss={loss[last]:.4f}") in out

    def test_registry_shared_with_telemetry_hook(self, matrix_setup,
                                                 capsys):
        tel = TelemetryHook()
        eng = make_spec_factory(matrix_setup, "sync", "flat")(
            [tel], verbose=True).build()
        eng.run()
        verbose = next(h for h in eng.hooks
                       if type(h).__name__ == "VerboseHook")
        assert verbose.registry is tel.registry  # one source of truth
        # idempotent capture: one row per round despite two consumers
        assert [r["round"] for r in tel.registry.rounds] == list(range(ROUNDS))
        assert capsys.readouterr().out.count("[heterosel] round") == 2

    def test_standalone_registry_when_no_telemetry(self, verbose_run):
        eng, _, _ = verbose_run
        verbose = next(h for h in eng.hooks
                       if type(h).__name__ == "VerboseHook")
        assert isinstance(verbose.registry, MetricsRegistry)
        assert len(verbose.registry.rounds) == ROUNDS
        row = verbose.registry.rounds[0]
        assert row["n_selected"] == 3 and row["bytes_up_wan"] > 0
