"""Distribution-layer tests on a small forced-device-count mesh.

These must run in a subprocess: the main pytest process keeps the real
single-device view (conftest.py), while the child sets
XLA_FLAGS=--xla_force_host_platform_device_count=8 before importing jax —
the same pattern launch/dryrun.py uses for the 512-device production mesh.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the child's own subprocess timeout (560 s) must fire before the test's
pytestmark = pytest.mark.time_limit(600)


def run_child(body: str, timeout: int = 560) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.launch.steps import build_plan
        from repro.configs.registry import get_config, smoke_variant, get_shape
        import dataclasses
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.parametrize("arch,shape_name", [
    ("yi-9b", "train_4k"),
    ("grok-1-314b", "train_4k"),       # MoE shard_map under jit
    ("mamba2-370m", "decode_32k"),     # SSM state cache decode
    ("zamba2-7b", "prefill_32k"),      # hybrid super-blocks
])
def test_single_pod_small_mesh_compiles(arch, shape_name):
    """Reduced configs lower+compile on a (2,4) data×model mesh and the
    compiled module contains collectives (proof the mesh axes are used)."""
    run_child(f"""
        mesh = make_test_mesh((2, 4), ("data", "model"))
        cfg = smoke_variant(get_config("{arch}"))
        # widen so dims divide the (2,4) mesh
        cfg = dataclasses.replace(cfg, d_model=256, num_heads=4,
                                  num_kv_heads=4 if cfg.num_kv_heads else 0,
                                  head_dim=64 if cfg.num_heads else 0,
                                  d_ff=256 if cfg.d_ff else 0)
        shape = dataclasses.replace(get_shape("{shape_name}"),
                                    seq_len=64, global_batch=8)
        plan = build_plan(cfg, shape, mesh, fsdp=False)
        with jax.set_mesh(mesh):
            compiled = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                               out_shardings=plan.out_shardings,
                               donate_argnums=plan.donate_argnums).lower(*plan.args).compile()
        txt = compiled.as_text()
        assert any(c in txt for c in ("all-reduce", "all-gather", "reduce-scatter",
                                      "collective-permute", "all-to-all")), "no collectives!"
        print("OK", compiled.memory_analysis().temp_size_in_bytes)
    """)


def test_multi_pod_round_step_semantics():
    """The vmapped 2-client fed_round_step must equal the sequential
    two-client FedProx step + FedAvg computed without any mesh."""
    run_child("""
        import numpy as np
        from repro.models import build_model
        from repro.fed.client import fedprox_grad, sgd_step
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = smoke_variant(get_config("yi-9b"))
        cfg = dataclasses.replace(cfg, d_model=128, num_heads=4, num_kv_heads=4,
                                  head_dim=32, d_ff=128)
        shape = dataclasses.replace(get_shape("train_4k"), seq_len=32, global_batch=4)
        plan = build_plan(cfg, shape, mesh, multi_pod=True, fsdp=False)

        model = build_model(cfg)
        key = jax.random.PRNGKey(0)
        p = model.init_params(key)
        stacked = jax.tree.map(lambda a: jnp.stack([a, a * 1.01]), p)
        batch = {
            "tokens": jax.random.randint(key, (2, 4, 32), 0, cfg.vocab_size),
            "labels": jax.random.randint(key, (2, 4, 32), 0, cfg.vocab_size),
        }
        with jax.set_mesh(mesh):
            out, loss = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                                out_shardings=plan.out_shardings)(stacked, p, batch)

        # reference: sequential clients, no mesh
        mu, lr = 0.1, 0.01
        refs = []
        for i in range(2):
            pi = jax.tree.map(lambda a: a[i], stacked)
            bi = jax.tree.map(lambda a: a[i], batch)
            _, g = fedprox_grad(model.loss, pi, p, bi, mu)
            refs.append(sgd_step(pi, g, lr))
        ref = jax.tree.map(lambda a, b: (a.astype(jnp.float32) + b.astype(jnp.float32)) / 2, *refs)
        err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                  for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)))
        print("max err", err)
        assert err < 2e-2, err
        print("OK")
    """)


def test_production_mesh_shapes():
    run_child("""
        # only mesh construction — no compile (512-dev meshes are the
        # launcher's job; here we check the factory math with 8 devices)
        from repro.launch.mesh import mesh_chip_count
        m = make_test_mesh((2, 4), ("data", "model"))
        assert m.axis_names == ("data", "model")
        assert mesh_chip_count(m) == 8
        m2 = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        assert mesh_chip_count(m2) == 8
        print("OK")
    """)


def test_moe_a2a_matches_gather_and_local():
    """The two expert-parallel impls and the meshless reference agree."""
    run_child("""
        import numpy as np
        from repro.models import moe as M
        mesh = make_test_mesh((2, 4), ("data", "model"))
        cfg = smoke_variant(get_config("grok-1-314b"))
        cfg = dataclasses.replace(cfg, d_model=64, d_ff=64, num_experts=4,
                                  num_experts_per_tok=2, moe_capacity_factor=4.0)
        key = jax.random.PRNGKey(0)
        lp = M.init_moe_ffn(key, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64), jnp.float32)
        ref, _ = M._moe_ffn_local(cfg, lp, x, model_axis=None, fsdp_axis=None)
        with jax.set_mesh(mesh):
            g, _ = jax.jit(lambda l, xx: M.moe_ffn(cfg, l, xx, mesh=mesh))(lp, x)
            cfg2 = dataclasses.replace(cfg, moe_impl="a2a")
            a, _ = jax.jit(lambda l, xx: M.moe_ffn(cfg2, l, xx, mesh=mesh))(lp, x)
        assert float(jnp.abs(g - ref).max()) < 1e-4
        assert float(jnp.abs(a - ref).max()) < 1e-4
        print("OK")
    """)


def test_decode_cache_seq_sharding_rule():
    """GQA caches with KVH < |model| sequence-shard over 'model' (§Perf)."""
    run_child("""
        from repro.sharding import rules
        mesh = make_test_mesh((2, 4), ("data", "model"))
        cfg = get_config("yi-9b")  # KVH=4 < 4? equals — craft KVH=2
        cfg = dataclasses.replace(cfg, num_kv_heads=2)
        cache = {"k": jax.ShapeDtypeStruct((4, 8, 64, 2, 128), jnp.bfloat16),
                 "v": jax.ShapeDtypeStruct((4, 8, 64, 2, 128), jnp.bfloat16)}
        specs = rules.cache_specs(cache, cfg, mesh)
        # B=8 divisible by data(2); KVH=2 not divisible by model(4);
        # T=64 divisible -> sequence-sharded
        assert specs["k"] == P(None, "data", "model", None, None), specs["k"]
        print("OK")
    """)
