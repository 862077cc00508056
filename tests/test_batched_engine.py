"""Batched client-execution engine tests (ISSUE 2 tentpole).

Equivalence contract (docs/engine.md §3): the batched path computes
the same per-client updates as the sequential reference — exactly on
matmul-family models, and to float tolerance on conv nets (XLA lowers the
vmapped per-client-weights conv differently, and GN/ReLU amplify ulp-level
differences across SGD steps). Selection histories must match exactly at
K=12/same seed; the large-K path must feed the struct-of-arrays state to the
fused Pallas scoring kernel.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import shared_run, tiny_model
from repro.configs.base import FedConfig
from repro.core.scoring import HeteRoScoreConfig, compute_scores
from repro.core.selection import SelectorConfig, dynamic_temperature, make_selector
from repro.core.state import (
    init_client_state,
    scatter_observations,
    score_inputs,
    update_client_state,
)
from repro.data import make_lazy_vision_data, make_vision_data
from repro.fed import batched as fb
from repro.fed import client as fc
from repro.fed import server as fs
from repro.fed import run_federated
from repro.kernels.score_select import fused_score_probs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def quad_loss(params, batch):
    return 0.5 * jnp.sum((params["w"] - batch["c"]) ** 2)


def quad_cohort(m=6, steps=5, dim=16):
    key = jax.random.PRNGKey(0)
    params = {"w": jnp.zeros(dim)}
    batches = [
        {"c": jax.random.normal(jax.random.fold_in(key, i), (steps, dim))}
        for i in range(m)
    ]
    return params, batches


class TestEngineCore:
    def test_batched_equals_sequential_exactly_on_linear_model(self):
        params, batches = quad_cohort()
        seq = [fc.local_train(quad_loss, params, b, lr=0.05, mu=0.1) for b in batches]
        train = fb.make_batched_local_train(quad_loss, lr=0.05, mu=0.1)
        res = train(params, fb.stack_client_trees(batches))
        np.testing.assert_allclose(
            np.asarray(res.params["w"]),
            np.stack([np.asarray(r.params["w"]) for r in seq]), atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(res.mean_loss),
            np.asarray([float(r.mean_loss) for r in seq]), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(res.update_sqnorm),
            np.asarray([float(r.update_sqnorm) for r in seq]), rtol=1e-5)

    def test_fused_aggregation_matches_list_fedavg(self):
        params, batches = quad_cohort()
        seq = [fc.local_train(quad_loss, params, b, lr=0.05, mu=0.1) for b in batches]
        train = fb.make_batched_local_train(quad_loss, lr=0.05, mu=0.1)
        cohort = fb.train_clients_batched(train, params, fb.stack_client_trees(batches))
        np.testing.assert_allclose(
            np.asarray(cohort.avg_params["w"]),
            np.asarray(fs.fedavg([r.params for r in seq])["w"]), atol=1e-6)

    def test_chunked_matches_unchunked(self):
        params, batches = quad_cohort(m=7)  # 7 % 3 != 0 → exercises padding
        train = fb.make_batched_local_train(quad_loss, lr=0.05, mu=0.1)
        stacked = fb.stack_client_trees(batches)
        full = fb.train_clients_batched(train, params, stacked)
        chunked = fb.train_clients_batched(train, params, stacked, chunk=3)
        np.testing.assert_allclose(
            np.asarray(chunked.avg_params["w"]),
            np.asarray(full.avg_params["w"]), atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(chunked.mean_loss), np.asarray(full.mean_loss), rtol=1e-6)
        assert chunked.mean_loss.shape == (7,)

    def test_weighted_aggregation(self):
        stacked = {"w": jnp.stack([jnp.zeros(2), jnp.ones(2)])}
        out = fs.fedavg_fused(stacked, weights=jnp.asarray([1.0, 3.0]))
        np.testing.assert_allclose(np.asarray(out["w"]), 0.75)
        out_u = fs.fedavg_fused(stacked)
        np.testing.assert_allclose(np.asarray(out_u["w"]), 0.5)

    def test_server_momentum_stacked_matches_list(self):
        trees = [{"w": jnp.full(3, float(i))} for i in range(4)]
        stacked = {"w": jnp.stack([t["w"] for t in trees])}
        prev = {"w": jnp.zeros(3)}
        a = fs.ServerMomentum(beta=0.5).aggregate(prev, trees)
        b = fs.ServerMomentum(beta=0.5).aggregate_stacked(prev, stacked)
        np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]), atol=1e-6)

    def test_scatter_observations(self):
        idx = jnp.asarray([4, 1, 7])
        loss, sq = scatter_observations(9, idx, jnp.asarray([1.0, 2.0, 3.0]),
                                        jnp.asarray([4.0, 5.0, 6.0]))
        assert loss.shape == (9,) and sq.shape == (9,)
        np.testing.assert_allclose(np.asarray(loss)[[4, 1, 7]], [1.0, 2.0, 3.0])
        np.testing.assert_allclose(np.asarray(sq)[[4, 1, 7]], [4.0, 5.0, 6.0])
        assert float(jnp.sum(loss)) == pytest.approx(6.0)


@pytest.fixture(scope="module")
def vision_setup():
    fed = FedConfig(num_clients=12, participation=0.5, rounds=6, local_epochs=2,
                    local_batch=16, lr=0.3, mu=0.1, dirichlet_alpha=0.1, seed=0)
    data = make_vision_data(fed, train_per_class=48, test_per_class=16, noise=0.3)
    return fed, data, tiny_model()


class TestEndToEndEquivalence:
    def test_batched_matches_sequential_k12(self, vision_setup):
        """ISSUE 2 acceptance: same seed ⇒ identical selection histories and
        accuracies to float tolerance on the paper model at K=12."""
        fed, data, model = vision_setup
        rb = shared_run(run_federated, model, fed, data, steps_per_round=4,
                        client_execution="batched")
        rs = run_federated(model, fed, data, steps_per_round=4,
                           client_execution="sequential")
        assert (rb.selected_history == rs.selected_history).all()
        np.testing.assert_allclose(rb.accuracy, rs.accuracy, atol=0.05)
        np.testing.assert_allclose(rb.train_loss, rs.train_loss, atol=0.05)
        assert rb.selection_counts.sum() == fed.rounds * fed.num_selected

    def test_chunked_run_matches_batched(self, vision_setup):
        fed, data, model = vision_setup
        rb = shared_run(run_federated, model, fed, data, steps_per_round=4,
                        client_execution="batched")
        rc = run_federated(model, dataclasses.replace(fed, client_chunk=4), data,
                           steps_per_round=4, client_execution="batched")
        assert (rb.selected_history == rc.selected_history).all()
        np.testing.assert_allclose(rb.accuracy, rc.accuracy, atol=0.05)

    def test_bad_execution_mode_raises(self, vision_setup):
        fed, data, model = vision_setup
        with pytest.raises(ValueError, match="client_execution"):
            run_federated(model, fed, data, client_execution="warp")


class TestLargeKPallasPath:
    def k512_state(self):
        k = 512
        rng = np.random.default_rng(3)
        s = init_client_state(k, jnp.asarray(rng.uniform(0, 0.69, k), jnp.float32))
        return update_client_state(
            s, round_idx=jnp.int32(4),
            selected_mask=jnp.asarray(rng.uniform(size=k) > 0.6),
            observed_loss=jnp.asarray(rng.uniform(0.1, 4, k), jnp.float32),
            observed_sqnorm=jnp.asarray(rng.uniform(0, 2, k), jnp.float32),
        )

    def test_k512_state_feeds_fused_kernel(self):
        """ISSUE 2 acceptance: vectorized state → Pallas scoring at K=512."""
        s = self.k512_state()
        cfg = HeteRoScoreConfig()
        sel_cfg = SelectorConfig(num_selected=64)
        t = jnp.int32(5)
        tau = dynamic_temperature(t, sel_cfg)
        probs, scores = fused_score_probs(
            *score_inputs(s), round_idx=jnp.float32(5), tau=tau, cfg=cfg,
            interpret=True)
        ref_scores = compute_scores(s, t, cfg, additive=True)
        ref_probs = jax.nn.softmax(ref_scores / tau)
        np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_scores),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(probs), np.asarray(ref_probs),
                                   atol=2e-6)

    def test_heterosel_pallas_selector_k512(self):
        s = self.k512_state()
        sel = make_selector("heterosel_pallas", SelectorConfig(num_selected=64))
        mask, probs = jax.jit(sel)(jax.random.PRNGKey(0), s, jnp.int32(5))
        assert int(mask.sum()) == 64
        assert float(jnp.sum(probs)) == pytest.approx(1.0, abs=1e-5)
        # agrees with the jnp selector under the same key
        ref = make_selector("heterosel", SelectorConfig(num_selected=64))
        mask_ref, _ = jax.jit(ref)(jax.random.PRNGKey(0), s, jnp.int32(5))
        assert (np.asarray(mask) == np.asarray(mask_ref)).all()

    def test_lazy_10k_federation_cohort(self):
        fed = FedConfig(num_clients=10_000, dirichlet_alpha=0.1, seed=0)
        data = make_lazy_vision_data(fed, image_size=16, test_per_class=4)
        assert data.num_clients == 10_000
        assert data.label_js.shape == (10_000,)
        assert np.isfinite(data.label_js).all() and data.label_js.mean() > 0.1
        rng = np.random.default_rng(0)
        sel = rng.choice(10_000, size=16, replace=False)
        b = data.stacked_client_batches(sel, 2, 4, rng)
        assert b["images"].shape == (16, 2, 4, 16, 16, 3)
        assert b["labels"].shape == (16, 2, 4)
        # skew: a low-α client's draws concentrate on its dominant label
        labels = data._sample_labels(np.asarray([int(sel[0])]), 512, rng)[0]
        share = np.bincount(labels, minlength=10).max() / 512
        assert share >= data.label_dists[int(sel[0])].max() - 0.1


@pytest.mark.time_limit(600)  # above the child's own 560 s timeout
def test_pod_shard_map_matches_single_device():
    """The mesh path shards the cohort's client axis over 'pod' and must
    reproduce the single-device vmap result (subprocess: forced 8 devices)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.fed import batched as fb
        from repro.sharding import rules

        def quad_loss(params, batch):
            return 0.5 * jnp.sum((params["w"] - batch["c"]) ** 2)

        key = jax.random.PRNGKey(0)
        params = {"w": jnp.zeros(16)}
        batches = [{"c": jax.random.normal(jax.random.fold_in(key, i), (5, 16))}
                   for i in range(8)]
        stacked = fb.stack_client_trees(batches)

        plain = fb.make_batched_local_train(quad_loss, lr=0.05, mu=0.1)
        ref = plain(params, stacked)

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("pod",))
        sharded_train = fb.make_batched_local_train(
            quad_loss, lr=0.05, mu=0.1, mesh=mesh, axes=rules.POD_AXES)
        placed = fb.shard_cohort(stacked, mesh)
        res = sharded_train(params, placed)
        np.testing.assert_allclose(np.asarray(res.params["w"]),
                                   np.asarray(ref.params["w"]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(res.mean_loss),
                                   np.asarray(ref.mean_loss), atol=1e-6)
        cohort = fb.train_clients_batched(sharded_train, params, placed)
        np.testing.assert_allclose(np.asarray(cohort.avg_params["w"]),
                                   np.asarray(fb.train_clients_batched(
                                       plain, params, stacked).avg_params["w"]),
                                   atol=1e-6)
        # M=6 does not divide pod=8: pad_to pads with zero-weight repeats
        stacked6 = fb.stack_client_trees(batches[:6])
        c6 = fb.train_clients_batched(sharded_train, params, stacked6, pad_to=8)
        ref6 = fb.train_clients_batched(plain, params, stacked6)
        assert c6.mean_loss.shape == (6,)
        np.testing.assert_allclose(np.asarray(c6.avg_params["w"]),
                                   np.asarray(ref6.avg_params["w"]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(c6.mean_loss),
                                   np.asarray(ref6.mean_loss), atol=1e-6)
        print("POD-SHARD-OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560, env=env, cwd=REPO)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr[-3000:]}"
    assert "POD-SHARD-OK" in out.stdout
