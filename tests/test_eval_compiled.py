"""``default_eval`` runs one compiled program per model and eval shape.

The eval is traced on its first call and reused while only the parameters
change (the engine's rounds); a new eval shape traces it again. Its metric is
the eager formula's: accuracy over every image for classifiers, chunked or
not, and exp(-loss) for LM families."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.fed import engine as fed_engine
from repro.fed.engine import default_eval
from repro.models import build_model

from conftest import tiny_model

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def tiny_ssm():
    """Mamba-2 at two layers of width 64, chunk 16: the layer scan and the
    chunked SSD of the full model, at a CPU test's size."""
    return build_model(dataclasses.replace(
        get_config("mamba2-370m"), num_layers=2, d_model=64, vocab_size=256,
        ssm_state=16, ssm_headdim=16, ssm_chunk=16))


def image_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return {"images": jnp.asarray(rng.normal(size=(n, 8, 8, 3)), jnp.float32),
            "labels": jnp.asarray(rng.integers(0, 10, n), jnp.int32)}


def token_batch(n, seq=32, seed=0):
    toks = jnp.asarray(np.random.default_rng(seed).integers(0, 256, (n, seq)), jnp.int32)
    return {"tokens": toks, "labels": toks}


# family → (model, the Model field the eval calls, an eval batch of n rows)
CASES = {
    "resnet": (tiny_model, "forward", image_batch),
    "ssm": (tiny_ssm, "loss", token_batch),
}


def counted(model, field):
    """``model`` with ``field`` wrapped to count the times its Python body
    runs: once per trace under jit, once per call when run eagerly."""
    calls = []
    fn = getattr(model, field)

    def wrapped(*a, **k):
        calls.append(1)
        return fn(*a, **k)

    return dataclasses.replace(model, **{field: wrapped}), calls


class CompileCounter:
    """Backend compiles (or compile-cache loads) while registered."""

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


@pytest.mark.parametrize("family", list(CASES))
def test_traces_once_per_model_and_eval_shape(family):
    build, field, make_batch = CASES[family]
    model, calls = counted(build(), field)
    batch = make_batch(4)
    params = [model.init_params(jax.random.PRNGKey(i)) for i in range(3)]

    metrics = [default_eval(model, params[0], batch)]
    assert len(calls) == 1
    with CompileCounter() as compiles:
        metrics += [default_eval(model, p, batch) for p in params[1:]]
    assert len(calls) == 1, "new params, same eval shape: traced again"
    assert compiles.count == 0, "new params, same eval shape: compiled again"
    assert len(set(metrics)) > 1, "the params did not reach the eval"

    default_eval(model, params[0], make_batch(6))
    assert len(calls) == 2, "a new eval shape must trace its own program"


def test_plain_model_object_is_cached():
    """A model that is neither a ``Model`` nor a dataclass (the cache keys on
    the object itself) traces once as well."""
    inner = tiny_model()
    calls = []

    class Plain:
        cfg = inner.cfg
        init_params = staticmethod(inner.init_params)

        def forward(self, params, batch):
            calls.append(1)
            return inner.forward(params, batch)

    model, batch = Plain(), image_batch(4)
    for i in range(3):
        default_eval(model, model.init_params(jax.random.PRNGKey(i)), batch)
    assert len(calls) == 1


@pytest.mark.parametrize("n, per_chunk", [(10, 1000), (10, 4), (12, 4)],
                         ids=["one-chunk", "padded-chunks", "even-chunks"])
def test_accuracy_equals_eager_formula(n, per_chunk, monkeypatch):
    """Labels are the eager predictions for the first half of the images and
    wrong for the rest, so the eager accuracy is exactly 1/2 and an image lost,
    counted twice or taken from the padding moves the compiled one."""
    monkeypatch.setattr(fed_engine, "EVAL_IMAGES_PER_CHUNK", per_chunk)
    model = tiny_model()  # a model object of its own: a new program
    params = model.init_params(jax.random.PRNGKey(1))
    batch = image_batch(n, seed=3)
    pred = jnp.argmax(model.forward(params, batch), -1)
    half = jnp.arange(n) < n // 2
    batch["labels"] = jnp.where(half, pred, (pred + 1) % 10).astype(jnp.int32)

    eager = float(jnp.mean((jnp.argmax(model.forward(params, batch), -1)
                            == batch["labels"]).astype(jnp.float32)))
    assert eager == 0.5
    assert abs(default_eval(model, params, batch) - eager) <= 1.0 / n


def test_chunks_are_equal_and_cover_the_batch(monkeypatch):
    monkeypatch.setattr(fed_engine, "EVAL_IMAGES_PER_CHUNK", 1000)
    for n, want in [(10_000, (10, 1000)), (1000, (1, 1000)), (16, (1, 16)),
                    (1001, (2, 501)), (2999, (3, 1000))]:
        chunks, rows = fed_engine._eval_chunks(n)
        assert (chunks, rows) == want
        assert rows <= 1000 and n <= chunks * rows < n + chunks


def test_lm_metric_equals_eager_formula():
    model = tiny_ssm()
    params = model.init_params(jax.random.PRNGKey(2))
    batch = token_batch(4, seed=5)
    eager = float(jnp.exp(-model.loss(params, batch)))
    np.testing.assert_allclose(default_eval(model, params, batch), eager, rtol=1e-5)
