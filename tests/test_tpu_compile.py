"""Compile the main path for a described TPU v5e chip (no chip attached).

Interpret mode cannot see what Mosaic refuses (block shapes off the (8, 128)
tiling, primitives with no TPU lowering) nor whether a program fits the
chip's 16 GB, so these tests lower and compile the real programs for a
``v5e:2x2`` topology described in-process: the ``score_select`` kernels at a
multi-block K in bf16, the segmented kernel at E=32 edges, and the
full-width ResNet-18 batched cohort step at the paper's M=6.

The topology is described inside a module-scoped fixture (never at import),
so every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU compiler. The persistent compilation cache
is off around these compiles: entries written for a described chip cannot
be read back without one.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.scoring import HeteRoScoreConfig
from repro.kernels import score_select as ss

HBM_BYTES = 16 * 10**9  # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _state_shapes(k, sharding, bf16=True):
    fl = jnp.bfloat16 if bf16 else jnp.float32
    dtypes = (fl, fl, fl, jnp.int32, jnp.int32, fl, fl, fl)
    return [jax.ShapeDtypeStruct((k,), d, sharding=sharding) for d in dtypes]


def _scalar(sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((), dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel", ["probs", "select"])
def test_score_select_multi_block_compiles(one_chip, kernel):
    """K=10⁵ spans four 32768-client blocks: the partial tables and the
    Gumbel logits must use TPU-legal block shapes."""
    k, cfg = 100_000, HeteRoScoreConfig()
    rows = _state_shapes(k, one_chip)
    if kernel == "probs":
        fn = lambda t, tau, *r: ss.fused_score_probs(  # noqa: E731
            *r, round_idx=t, tau=tau, cfg=cfg)
        args = (_scalar(one_chip), _scalar(one_chip), *rows)
    else:
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
        fn = lambda t, tau, key, *r: ss.fused_score_select(  # noqa: E731
            *r, round_idx=t, tau=tau, m=256, key=key, cfg=cfg)
        args = (_scalar(one_chip), _scalar(one_chip), key, *rows)
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


def test_segmented_score_probs_compiles(one_chip):
    """E=32 edges in one grid=(E,) launch, each edge's size in its own
    LANE-wide block."""
    num_edges, seg = 32, 128
    fn = functools.partial(ss.segmented_score_probs, seg=seg,
                           cfg=HeteRoScoreConfig())
    sizes = jax.ShapeDtypeStruct((num_edges,), jnp.int32, sharding=one_chip)

    def call(sizes, t, tau, *rows):
        return fn(*rows, sizes=sizes, round_idx=t, tau=tau)

    compiled = _compile(call, sizes, _scalar(one_chip), _scalar(one_chip),
                        *_state_shapes(num_edges * seg, one_chip, bf16=False))
    assert "tpu_custom_call" in compiled.as_text()


def test_resnet18_cohort_step_fits_one_chip(one_chip):
    """The paper's cohort — M=6 clients × 4 FedProx steps × batch 16 of
    32×32×3 — through full-width ResNet-18 in one batched step."""
    from repro.configs.registry import get_config
    from repro.fed.batched import make_batched_local_train
    from repro.models import build_model

    model = build_model(get_config("resnet18-cifar10"))
    assert model.cfg.d_model == 64
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    m, steps, batch = 6, 4, 16
    batches = {
        "images": jax.ShapeDtypeStruct((m, steps, batch, 32, 32, 3),
                                       jnp.float32, sharding=one_chip),
        "labels": jax.ShapeDtypeStruct((m, steps, batch), jnp.int32,
                                       sharding=one_chip),
    }
    step = make_batched_local_train(model.loss, lr=0.3, mu=0.1)
    mem = step.lower(params, batches).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, f"{total / 1e9:.2f} GB"
