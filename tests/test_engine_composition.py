"""Composable round-engine API tests (ISSUE 3 tentpole), continued.

Covers the acceptance criteria:
  * aggregator parity — list FedAvg, weighted FedAvg(uniform) and the fused
    stacked reduction agree on random pytrees incl. mixed-dtype leaves;
  * compression composes with the batched schedule (int8) and refuses the
    incompatible pairing (top-k) loudly instead of silently switching;
  * round hooks, registry names and instances in ``FederatedSpec``, and the
    eval metric's name in ``FLResult``.

Golden equivalence and kill-and-resume are in ``test_engine_api.py``.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.configs.registry import get_config, smoke_variant
from repro.fed import (
    AdaptiveMuHook,
    CompressedExecutor,
    ExecutorCompatError,
    FedAvgM,
    FederatedSpec,
    RoundHook,
    SequentialExecutor,
    register_executor,
    run_federated,
)
from repro.fed import compression as comp
from repro.fed import server as fs
from repro.fed.engine import EXECUTORS
from repro.models import build_model


def random_mixed_trees(m=5, seed=0):
    """M client pytrees with f32 and bf16 leaves."""
    key = jax.random.PRNGKey(seed)
    trees = []
    for i in range(m):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
        trees.append({
            "dense": jax.random.normal(k1, (7, 3), jnp.float32),
            "half": jax.random.normal(k2, (4,), jnp.float32).astype(jnp.bfloat16),
            "nested": {"b": jax.random.normal(k3, (2, 2), jnp.float32)},
        })
    return trees


class TestAggregatorParity:
    """fedavg == fedavg_weighted(uniform) == fused stacked reduction, on
    random pytrees including mixed-dtype leaves."""

    def test_three_way_parity_mixed_dtypes(self):
        trees = random_mixed_trees(m=5)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
        a = fs.fedavg(trees)
        b = fs.fedavg_weighted(trees, [1.0] * len(trees))
        c = fs.fedavg_fused(stacked)
        w = jnp.full((len(trees),), 1.0 / len(trees), jnp.float32)
        d = fs.weighted_sum_stacked(stacked, w)  # f32 leaves, caller casts
        for la, lb, lc, ld in zip(*map(jax.tree_util.tree_leaves, (a, b, c, d))):
            assert la.dtype == lb.dtype == lc.dtype
            tol = 2e-2 if la.dtype == jnp.bfloat16 else 1e-6
            af = np.asarray(la, np.float32)
            np.testing.assert_allclose(np.asarray(lb, np.float32), af, atol=tol)
            np.testing.assert_allclose(np.asarray(lc, np.float32), af, atol=tol)
            np.testing.assert_allclose(np.asarray(ld, np.float32), af, atol=tol)

    def test_nonuniform_weighted_parity(self):
        trees = random_mixed_trees(m=4, seed=3)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
        w = [1.0, 2.0, 3.0, 4.0]
        a = fs.fedavg_weighted(trees, w)
        b = fs.fedavg_fused(stacked, weights=jnp.asarray(w))
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            tol = 2e-2 if la.dtype == jnp.bfloat16 else 1e-6
            np.testing.assert_allclose(np.asarray(lb, np.float32),
                                       np.asarray(la, np.float32), atol=tol)


class TestCompressionComposition:
    """Satellite: no silent compression ⇒ sequential downgrade."""

    def test_int8_stacked_matches_per_client(self):
        trees = random_mixed_trees(m=3, seed=1)
        f32_trees = [jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), t) for t in trees]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *f32_trees)
        c_stacked, stats = comp.quantize_int8_stacked(stacked)
        back = comp.dequantize_int8_stacked(c_stacked)
        wire_ref = 0
        for i, t in enumerate(f32_trees):
            c_i, stats_i = comp.quantize_int8(t)
            wire_ref += stats_i.wire_bytes
            for ls, lr in zip(jax.tree_util.tree_leaves(
                    jax.tree_util.tree_map(lambda x: x[i], back)),
                    jax.tree_util.tree_leaves(comp.dequantize_int8(c_i))):
                np.testing.assert_allclose(np.asarray(ls), np.asarray(lr),
                                           atol=1e-7)
        assert stats.wire_bytes == wire_ref
        # tiny test leaves make the per-client scale overhead visible; the
        # ~4x ratio on real tensors is asserted end-to-end below
        assert stats.wire_bytes < stats.raw_bytes

    def test_int8_composes_with_batched(self, small_setup):
        fed, data, model = small_setup
        res = run_federated(model, fed, data, selector="heterosel",
                            steps_per_round=2, compression="int8",
                            client_execution="batched")
        assert res.wire_bytes > 0
        assert res.raw_bytes / res.wire_bytes > 3.5
        assert np.isfinite(res.accuracy).all()

    def test_topk_explicit_batched_raises(self, small_setup):
        fed, data, model = small_setup
        with pytest.raises(ExecutorCompatError, match="sequential"):
            run_federated(model, fed, data, compression="topk",
                          client_execution="batched")

    def test_topk_config_default_warns_and_downgrades(self, small_setup):
        fed, data, model = small_setup
        assert fed.client_execution == "batched"
        with pytest.warns(UserWarning, match="sequential"):
            res = run_federated(model, fed, data, selector="heterosel",
                                steps_per_round=2, compression="topk")
        assert res.wire_bytes > 0
        assert np.isfinite(res.accuracy).all()

    def test_topk_residuals_live_on_executor(self, small_setup):
        fed, data, model = small_setup
        spec = FederatedSpec(model, fed, data, selector="heterosel",
                             steps_per_round=2, executor="sequential",
                             compression="topk")
        engine = spec.build()
        assert isinstance(engine.executor, CompressedExecutor)
        engine.run()
        assert len(engine.executor.residuals) > 0  # error feedback persisted

    def test_int8_chunked_batched_raises(self, small_setup):
        fed, data, model = small_setup
        fed = dataclasses.replace(fed, client_chunk=2)
        with pytest.raises(ExecutorCompatError, match="client_chunk"):
            FederatedSpec(model, fed, data, compression="int8",
                          executor="batched").build()

    def test_int8_chunked_config_default_warns_and_downgrades(self, small_setup):
        """Legacy back-compat: run_federated(compression='int8') worked with
        any config pre-refactor — a chunked config must not start raising."""
        fed, data, model = small_setup
        fed = dataclasses.replace(fed, client_chunk=2)
        with pytest.warns(UserWarning, match="sequential"):
            res = run_federated(model, fed, data, selector="heterosel",
                                steps_per_round=2, compression="int8")
        assert res.wire_bytes > 0
        assert np.isfinite(res.accuracy).all()


class CountingHook(RoundHook):
    def __init__(self):
        self.run_start = self.run_end = 0
        self.round_start = self.round_end = 0
        self.seen_metrics = []

    def on_run_start(self, ctx):
        self.run_start += 1

    def on_round_start(self, ctx):
        self.round_start += 1

    def on_round_end(self, ctx):
        self.round_end += 1
        self.seen_metrics.append(ctx.metric)
        assert ctx.selected is not None and len(ctx.selected) > 0
        assert ctx.obs_loss.shape == (ctx.fed.num_clients,)

    def on_run_end(self, ctx):
        self.run_end += 1

    def contribute(self, extras):
        extras["counted"] = self.round_end


class TestHooksAndSpec:
    def test_hook_lifecycle_and_context(self, small_setup):
        fed, data, model = small_setup
        hook = CountingHook()
        res = FederatedSpec(model, fed, data, selector="heterosel",
                            steps_per_round=2, hooks=[hook]).build().run()
        assert hook.run_start == hook.run_end == 1
        assert hook.round_start == hook.round_end == fed.rounds
        np.testing.assert_allclose(hook.seen_metrics, res.accuracy)

    def test_adaptive_mu_hook_matches_wrapper_kwarg(self, small_setup):
        fed, data, model = small_setup
        r1 = run_federated(model, fed, data, selector="heterosel",
                           steps_per_round=2, adaptive_mu=True)
        r2 = FederatedSpec(model, fed, data, selector="heterosel",
                           steps_per_round=2,
                           hooks=["adaptive_mu"]).build().run()
        assert r1.mu_history is not None and r2.mu_history is not None
        np.testing.assert_allclose(r1.mu_history, r2.mu_history)
        np.testing.assert_array_equal(r1.accuracy, r2.accuracy)

    def test_adaptive_mu_hook_instance(self, small_setup):
        fed, data, model = small_setup
        hook = AdaptiveMuHook()
        FederatedSpec(model, fed, data, selector="heterosel",
                      steps_per_round=2, hooks=[hook]).build().run()
        assert len(hook.history) == fed.rounds

    def test_unknown_names_raise(self, small_setup):
        fed, data, model = small_setup
        with pytest.raises(ValueError, match="client_execution"):
            FederatedSpec(model, fed, data, executor="warp").build()
        with pytest.raises(ValueError, match="aggregator"):
            FederatedSpec(model, fed, data, aggregator="fedmedian").build()
        with pytest.raises(ValueError, match="hook"):
            FederatedSpec(model, fed, data, hooks=["no_such_hook"]).build()

    def test_custom_executor_registers(self, small_setup):
        fed, data, model = small_setup

        @register_executor("sequential_copy")
        def _make(spec):
            return SequentialExecutor(spec)

        try:
            engine = FederatedSpec(model, fed, data,
                                   executor="sequential_copy").build()
            assert engine.executor.kind == "sequential"
        finally:
            EXECUTORS.pop("sequential_copy", None)

    def test_executor_instance_accepted(self, small_setup):
        fed, data, model = small_setup
        spec = FederatedSpec(model, fed, data, selector="heterosel",
                             steps_per_round=2)
        spec2 = dataclasses.replace(spec, executor=SequentialExecutor(spec))
        res = spec2.build().run()
        assert len(res.accuracy) == fed.rounds

    def test_fedavgm_aggregator_instance(self, small_setup):
        fed, data, model = small_setup
        agg = FedAvgM(beta=0.5)
        res = FederatedSpec(model, fed, data, selector="heterosel",
                            steps_per_round=2, aggregator=agg).build().run()
        assert agg.get_state() is not None  # velocity built over the run
        assert np.isfinite(res.accuracy).all()


class TestMetricNaming:
    """Satellite: _default_eval's overloaded return is named in FLResult."""

    def test_resnet_metric_is_accuracy(self, small_setup):
        fed, data, model = small_setup
        engine = FederatedSpec(model, fed, data).build()
        assert engine.metric_name == "accuracy"

    def test_lm_metric_is_not_called_accuracy(self):
        cfg = smoke_variant(get_config("qwen2-0.5b"))
        model = build_model(cfg)
        fed = FedConfig(num_clients=4, rounds=2)
        data_stub = type("D", (), {"num_clients": 4,
                                   "label_js": np.zeros(4, np.float32)})()
        engine = FederatedSpec(model, fed, data_stub).build()
        assert engine.metric_name == "exp(-loss)"

    def test_custom_eval_and_override(self, small_setup):
        fed, data, model = small_setup
        eng = FederatedSpec(model, fed, data,
                            eval_fn=lambda m, p, b: 0.0).build()
        assert eng.metric_name == "metric"
        eng2 = FederatedSpec(model, fed, data, eval_fn=lambda m, p, b: 0.0,
                             metric_name="f1").build()
        assert eng2.metric_name == "f1"

    def test_labeled_summary_names_metric(self, small_setup):
        fed, data, model = small_setup
        res = FederatedSpec(model, fed, data, selector="heterosel",
                            steps_per_round=2).build().run()
        assert res.metric_name == "accuracy"
        ls = res.labeled_summary()
        assert "peak_accuracy" in ls and "final_accuracy" in ls
        assert ls["peak_accuracy"] == res.summary()["peak_acc"]
