"""Production-mesh regression test: one real (arch × shape) lower+compile on
the actual 16×16 / 2×16×16 meshes with 512 forced host devices — the exact
code path `launch/dryrun.py` ships, guarded in-tree so a sharding-rule
regression cannot land silently. Subprocess-isolated like the small-mesh
tests (the parent keeps 1 device)."""

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
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        import jax
        from repro.launch.mesh import make_production_mesh, mesh_chip_count
        from repro.launch.steps import build_plan
        from repro.configs.registry import get_config, get_shape
        from repro.sharding.rules import needs_fsdp
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=REPO)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr[-3000:]}"
    return out.stdout


def test_single_pod_production_compile():
    """qwen2 × train_4k compiles on the real 256-chip mesh with collectives."""
    run_child("""
        mesh = make_production_mesh()
        assert mesh_chip_count(mesh) == 256
        cfg = get_config("qwen2-0.5b")
        plan = build_plan(cfg, get_shape("train_4k"), mesh,
                          fsdp=needs_fsdp(cfg, 16))
        with jax.set_mesh(mesh):
            compiled = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                               out_shardings=plan.out_shardings,
                               donate_argnums=plan.donate_argnums
                               ).lower(*plan.args).compile()
        txt = compiled.as_text()
        assert "all-reduce" in txt or "reduce-scatter" in txt
        print("OK", compiled.memory_analysis().temp_size_in_bytes)
    """)


def test_multi_pod_production_compile():
    """mamba2 fed_round_step compiles on the 512-chip two-pod mesh and the
    cross-pod FedAvg collective is present."""
    run_child("""
        mesh = make_production_mesh(multi_pod=True)
        assert mesh_chip_count(mesh) == 512
        cfg = get_config("mamba2-370m")
        plan = build_plan(cfg, get_shape("train_4k"), mesh, multi_pod=True,
                          fsdp=needs_fsdp(cfg, 16))
        with jax.set_mesh(mesh):
            compiled = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                               out_shardings=plan.out_shardings,
                               donate_argnums=plan.donate_argnums
                               ).lower(*plan.args).compile()
        assert "all-reduce" in compiled.as_text()
        print("OK")
    """)
