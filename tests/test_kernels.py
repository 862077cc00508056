"""Per-kernel validation (brief deliverable c): sweep shapes/dtypes in
interpret mode and assert_allclose against the pure-jnp oracles in ref.py."""

import numpy as np
import pytest

try:  # optional: property tests skip cleanly when hypothesis is absent
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    hypothesis = st = None

import jax
import jax.numpy as jnp

from repro.core.scoring import HeteRoScoreConfig, compute_scores
from repro.core.selection import (SelectorConfig, dynamic_temperature,
                                  sample_clients)
from repro.core.state import (init_client_state, to_bf16, to_f32,
                              update_client_state)
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("s,t,h,kvh,d", [
        (64, 64, 4, 4, 32),      # MHA square
        (128, 128, 4, 2, 64),    # GQA
        (96, 160, 2, 1, 16),     # MQA, uneven, padded blocks
        (32, 256, 8, 8, 128),    # short q, long kv, MXU-width head
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_shapes_dtypes(self, s, t, h, kvh, d, dtype, causal):
        if causal and s > t:
            pytest.skip("causal requires s<=t alignment here")
        k1, k2, k3 = jax.random.split(KEY, 3)
        q = jax.random.normal(k1, (2, s, h, d), dtype)
        k = jax.random.normal(k2, (2, t, kvh, d), dtype)
        v = jax.random.normal(k3, (2, t, kvh, d), dtype)
        out = ops.flash_mha(q, k, v, causal=causal, interpret=True)
        kf = jnp.repeat(k, h // kvh, 2)
        vf = jnp.repeat(v, h // kvh, 2)
        expect = ref.mha_reference(
            q.transpose(0, 2, 1, 3).reshape(2 * h, s, d),
            kf.transpose(0, 2, 1, 3).reshape(2 * h, t, d),
            vf.transpose(0, 2, 1, 3).reshape(2 * h, t, d),
            causal=causal,
        ).reshape(2, h, s, d).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))

    @pytest.mark.parametrize("window", [8, 32, 100])
    def test_sliding_window(self, window):
        q = jax.random.normal(KEY, (1, 128, 2, 32))
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 128, 2, 32))
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 128, 2, 32))
        out = ops.flash_mha(q, k, v, causal=True, window=window, interpret=True)
        expect = ref.mha_reference(
            q.transpose(0, 2, 1, 3).reshape(2, 128, 32),
            k.transpose(0, 2, 1, 3).reshape(2, 128, 32),
            v.transpose(0, 2, 1, 3).reshape(2, 128, 32),
            causal=True, window=window,
        ).reshape(1, 2, 128, 32).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32),
            atol=2e-5, rtol=2e-5)

    def test_matches_model_blockwise_path(self):
        """Kernel ≡ the model's jnp blockwise attention (swap-in safety)."""
        from repro.models.attention import blockwise_attention
        q = jax.random.normal(KEY, (2, 64, 4, 32))
        k = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 64, 2, 32))
        v = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 64, 2, 32))
        out_kernel = ops.flash_mha(q, k, v, causal=True, interpret=True)
        out_model = blockwise_attention(q, k, v, causal=True, kv_chunk=16)
        np.testing.assert_allclose(
            np.asarray(out_kernel, np.float32), np.asarray(out_model, np.float32),
            atol=2e-5, rtol=2e-5)


class TestSSDScan:
    @pytest.mark.parametrize("s,nh,hp,n,chunk", [
        (64, 2, 16, 8, 16),
        (96, 3, 32, 16, 32),   # padded last chunk
        (128, 1, 64, 32, 128), # single chunk
    ])
    def test_against_exact_recurrence(self, s, nh, hp, n, chunk):
        k1, k2, k3, k4, k5 = jax.random.split(KEY, 5)
        x = jax.random.normal(k1, (2, s, nh, hp))
        dt = jax.nn.softplus(jax.random.normal(k2, (2, s, nh)))
        a_neg = -jnp.exp(jax.random.normal(k3, (nh,)) * 0.3)
        b_in = jax.random.normal(k4, (2, s, n)) * 0.5
        c_in = jax.random.normal(k5, (2, s, n)) * 0.5
        y, h = ops.ssd_forward(x, dt, a_neg, b_in, c_in, chunk=chunk, interpret=True)
        y_ref, h_ref = ref.ssd_reference(x, dt, a_neg, b_in, c_in)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-3, rtol=2e-3)

    def test_matches_model_ssd_path(self):
        """Kernel composition ≡ the model's _ssd_chunked (swap-in safety)."""
        from repro.models.mamba2 import _ssd_chunked
        k1, k2, k3, k4, k5 = jax.random.split(jax.random.fold_in(KEY, 9), 5)
        x = jax.random.normal(k1, (1, 64, 2, 16))
        dt = jax.nn.softplus(jax.random.normal(k2, (1, 64, 2)))
        a_neg = -jnp.exp(jax.random.normal(k3, (2,)) * 0.3)
        b_in = jax.random.normal(k4, (1, 64, 8)) * 0.5
        c_in = jax.random.normal(k5, (1, 64, 8)) * 0.5
        y_k, h_k = ops.ssd_forward(x, dt, a_neg, b_in, c_in, chunk=16, interpret=True)
        y_m, h_m = _ssd_chunked(x, dt, a_neg, b_in, c_in, 16)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m), atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_m), atol=1e-4, rtol=1e-4)


class TestScoreSelectKernel:
    @pytest.mark.parametrize("k", [12, 100, 500, 1000])
    def test_fused_matches_paper_scoring(self, k):
        rng = np.random.default_rng(k)
        s = init_client_state(k, jnp.asarray(rng.uniform(0, 0.69, k), jnp.float32))
        for t in range(3):
            s = update_client_state(
                s, round_idx=jnp.int32(t),
                selected_mask=jnp.asarray(rng.uniform(size=k) > 0.4),
                observed_loss=jnp.asarray(rng.uniform(0.1, 4, k), jnp.float32),
                observed_sqnorm=jnp.asarray(rng.uniform(0, 2, k), jnp.float32),
            )
        cfg = HeteRoScoreConfig()
        t = jnp.int32(17)
        tau = dynamic_temperature(t, SelectorConfig())
        p, sc = ops.heterosel_probs(s, t, tau, cfg, interpret=True)
        p_ref, sc_ref = ref.score_probs_reference(s, t, tau, cfg)
        np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_ref), atol=2e-5)
        np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref), atol=2e-6)
        assert float(jnp.sum(p)) == pytest.approx(1.0, abs=1e-5)

    if hypothesis is None:
        def test_fused_probs_property(self):
            pytest.importorskip("hypothesis")
    else:
        @hypothesis.given(seed=st.integers(0, 1000), t=st.integers(0, 150))
        @hypothesis.settings(deadline=None, max_examples=10)
        def test_fused_probs_property(self, seed, t):
            self._fused_probs_property(seed, t)

    def _fused_probs_property(self, seed, t):
        rng = np.random.default_rng(seed)
        k = 64
        s = init_client_state(k, jnp.asarray(rng.uniform(0, 0.69, k), jnp.float32))
        s = update_client_state(
            s, round_idx=jnp.int32(0),
            selected_mask=jnp.asarray(rng.uniform(size=k) > 0.5),
            observed_loss=jnp.asarray(rng.uniform(0.01, 9, k), jnp.float32),
            observed_sqnorm=jnp.asarray(rng.uniform(0, 5, k), jnp.float32),
        )
        cfg = HeteRoScoreConfig()
        tau = dynamic_temperature(jnp.int32(t), SelectorConfig())
        p, _ = ops.heterosel_probs(s, jnp.int32(t), tau, cfg, interpret=True)
        assert bool(jnp.all(p >= 0)) and bool(jnp.all(jnp.isfinite(p)))
        assert float(jnp.sum(p)) == pytest.approx(1.0, abs=1e-5)


class TestMultiBlockScoreSelect:
    """PR 6 selection control plane: multi-block two-pass grid, bf16 SoA,
    staleness override, in-kernel Gumbel-top-m, segmented + sharded paths.
    ``block`` shrinks the VMEM block so small K exercises many blocks."""

    @staticmethod
    def _mid_state(k, seed=0, rounds=3):
        rng = np.random.default_rng(seed)
        s = init_client_state(k, jnp.asarray(rng.uniform(0, 0.69, k), jnp.float32))
        for t in range(rounds):
            s = update_client_state(
                s, round_idx=jnp.int32(t),
                selected_mask=jnp.asarray(rng.uniform(size=k) < 0.6),
                observed_loss=jnp.asarray(rng.uniform(0.1, 4, k), jnp.float32),
                observed_sqnorm=jnp.asarray(rng.uniform(0, 2, k), jnp.float32),
            )
        return s

    @pytest.mark.parametrize("k,block", [(300, 128), (515, 128), (1000, 256)])
    def test_multi_block_matches_reference(self, k, block):
        """K % 128 ≠ 0 spanning several blocks ≡ the jnp paper scoring."""
        s = self._mid_state(k, seed=k)
        cfg = HeteRoScoreConfig()
        t = jnp.int32(11)
        tau = dynamic_temperature(t, SelectorConfig())
        p, sc = ops.heterosel_probs(s, t, tau, cfg, interpret=True, block=block)
        p_ref, sc_ref = ref.score_probs_reference(s, t, tau, cfg)
        np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_ref), atol=2e-5)
        np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref), atol=2e-6)

    if hypothesis is None:
        def test_multi_block_property(self):
            pytest.importorskip("hypothesis")
    else:
        @hypothesis.given(
            k=st.integers(129, 600).filter(lambda k: k % 128 != 0),
            seed=st.integers(0, 100), t=st.integers(0, 99))
        @hypothesis.settings(deadline=None, max_examples=8)
        def test_multi_block_property(self, k, seed, t):
            self._multi_block_property(k, seed, t)

    def _multi_block_property(self, k, seed, t):
        s = self._mid_state(k, seed=seed, rounds=1)
        cfg = HeteRoScoreConfig()
        tau = dynamic_temperature(jnp.int32(t), SelectorConfig())
        p, _ = ops.heterosel_probs(s, jnp.int32(t), tau, cfg,
                                   interpret=True, block=128)
        p_ref, _ = ref.score_probs_reference(s, jnp.int32(t), tau, cfg)
        np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref), atol=2e-6)
        assert float(jnp.sum(p)) == pytest.approx(1.0, abs=1e-5)

    def test_bf16_all_never_selected(self):
        """The NEVER sentinel lives in the untouched int32 rows, so a fresh
        all-never-selected federation survives the bf16 round-trip and
        scores neutral/uniform off the compact state."""
        k = 260
        s = init_client_state(k, jnp.zeros(k))
        sb = to_bf16(s)
        assert sb.loss_prev.dtype == jnp.bfloat16
        assert sb.last_selected.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(to_f32(sb).last_selected),
                                      np.asarray(s.last_selected))
        cfg = HeteRoScoreConfig()
        t = jnp.int32(0)
        tau = dynamic_temperature(t, SelectorConfig())
        p, _ = ops.heterosel_probs(sb, t, tau, cfg, interpret=True, block=128)
        p_ref, _ = ref.score_probs_reference(s, t, tau, cfg)
        np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref), atol=2e-6)
        np.testing.assert_allclose(np.asarray(p), np.full(k, 1.0 / k), atol=2e-6)

    def test_staleness_override_parity(self):
        """Kernel fed a clock-derived (K,) Δ ≡ jnp scoring with the same
        override — the async-engine contract."""
        k = 300
        s = self._mid_state(k, seed=5)
        rng = np.random.default_rng(9)
        stale = jnp.asarray(rng.uniform(0, 30, k), jnp.float32)
        cfg = HeteRoScoreConfig()
        t = jnp.int32(21)
        tau = dynamic_temperature(t, SelectorConfig())
        p, sc = ops.heterosel_probs(s, t, tau, cfg, staleness_override=stale,
                                    interpret=True, block=128)
        sc_ref = compute_scores(s, t, cfg, additive=True,
                                staleness_override=stale)
        np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_ref), atol=2e-5)
        np.testing.assert_allclose(np.asarray(p),
                                   np.asarray(jax.nn.softmax(sc_ref / tau)),
                                   atol=2e-6)

    def test_topm_matches_sample_clients(self):
        """In-kernel Gumbel-top-m ≡ host-side sample_clients on one key."""
        k, m = 515, 24
        s = self._mid_state(k, seed=3)
        cfg = HeteRoScoreConfig()
        t = jnp.int32(9)
        tau = dynamic_temperature(t, SelectorConfig(num_selected=m))
        key = jax.random.PRNGKey(42)
        sel, p, _ = ops.heterosel_topm(s, t, tau, m, key, cfg,
                                       interpret=True, block=128)
        p_ref, _ = ref.score_probs_reference(s, t, tau, cfg)
        mask = sample_clients(key, p_ref, m)
        np.testing.assert_array_equal(np.sort(np.asarray(sel)),
                                      np.asarray(jnp.flatnonzero(mask)))
        np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref), atol=2e-6)

    def test_segmented_matches_per_edge_reference(self):
        """One segmented launch ≡ per-edge jnp softmax; padding lanes are
        exactly zero (the hierarchy's inner-selection contract)."""
        sizes = np.array([5, 128, 60], np.int32)
        seg = 128
        k = int(sizes.sum())
        s = self._mid_state(k, seed=13)
        perm = np.zeros(len(sizes) * seg, np.int64)
        off = 0
        for e, n in enumerate(sizes):
            perm[e * seg:e * seg + n] = np.arange(off, off + n)
            off += n
        sstate = jax.tree_util.tree_map(lambda x: x[jnp.asarray(perm)], s)
        cfg = HeteRoScoreConfig()
        tau = dynamic_temperature(jnp.int32(6), SelectorConfig())
        p, _ = ops.heterosel_probs_segmented(
            sstate, jnp.asarray(sizes), round_idx=jnp.float32(6), tau=tau,
            cfg=cfg, seg=seg, interpret=True)
        p = np.asarray(p)
        off = 0
        for e, n in enumerate(sizes):
            estate = jax.tree_util.tree_map(
                lambda x: x[jnp.arange(off, off + n)], s)
            p_ref, _ = ref.score_probs_reference(
                estate, jnp.float32(6), tau, cfg)
            np.testing.assert_allclose(p[e * seg:e * seg + n],
                                       np.asarray(p_ref), atol=2e-6)
            np.testing.assert_array_equal(p[e * seg + n:(e + 1) * seg], 0.0)
            off += n

    @pytest.mark.time_limit(600)  # above the child's own 570 s timeout
    def test_sharded_topm_multi_device_subprocess(self):
        """A real 8-way client device axis reproduces the fused cohort
        (subprocess: XLA forced host devices, like the pod-mesh test)."""
        import os
        import subprocess
        import sys
        import textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import numpy as np
            import jax, jax.numpy as jnp
            from jax.sharding import Mesh
            from repro.core.scoring import HeteRoScoreConfig
            from repro.core.selection import SelectorConfig, dynamic_temperature
            from repro.core.state import init_client_state, update_client_state
            from repro.kernels import ops

            k, m = 1024, 16
            rng = np.random.default_rng(0)
            s = init_client_state(k, jnp.asarray(rng.uniform(0, 0.69, k), jnp.float32))
            s = update_client_state(
                s, round_idx=jnp.int32(0),
                selected_mask=jnp.asarray(rng.uniform(size=k) < 0.6),
                observed_loss=jnp.asarray(rng.uniform(0.1, 4, k), jnp.float32),
                observed_sqnorm=jnp.asarray(rng.uniform(0, 2, k), jnp.float32))
            cfg = HeteRoScoreConfig()
            t = jnp.int32(3)
            tau = dynamic_temperature(t, SelectorConfig(num_selected=m))
            key = jax.random.PRNGKey(11)
            sel_f, p_f, _ = ops.heterosel_topm(s, t, tau, m, key, cfg,
                                               interpret=True)
            mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("clients",))
            sel_s, p_s, _ = ops.heterosel_topm_sharded(
                s, t, tau, m, key, cfg, mesh=mesh, interpret=True)
            np.testing.assert_array_equal(np.sort(np.asarray(sel_f)),
                                          np.sort(np.asarray(sel_s)))
            np.testing.assert_allclose(np.asarray(p_f), np.asarray(p_s),
                                       atol=2e-6)
        """)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ, "PYTHONPATH": os.path.join(repo, "src")}
        out = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                             capture_output=True, text=True, timeout=570)
        assert out.returncode == 0, out.stderr

    def test_sharded_equals_fused_single_device(self):
        """shard_map wrapper on one device reproduces the fused kernel."""
        k, m = 384, 12
        s = self._mid_state(k, seed=8)
        cfg = HeteRoScoreConfig()
        t = jnp.int32(4)
        tau = dynamic_temperature(t, SelectorConfig(num_selected=m))
        key = jax.random.PRNGKey(5)
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("clients",))
        sel_f, p_f, _ = ops.heterosel_topm(s, t, tau, m, key, cfg,
                                           interpret=True, block=128)
        sel_s, p_s, _ = ops.heterosel_topm_sharded(
            s, t, tau, m, key, cfg, mesh=mesh, interpret=True, block=128)
        np.testing.assert_array_equal(np.sort(np.asarray(sel_f)),
                                      np.sort(np.asarray(sel_s)))
        np.testing.assert_allclose(np.asarray(p_f), np.asarray(p_s), atol=2e-6)
