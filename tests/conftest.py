"""Shared fixtures. NOTE: no XLA_FLAGS here by design — smoke tests and
benches must see the real (single) CPU device; only launch/dryrun.py forces
512 placeholder devices (brief, MULTI-POD DRY-RUN §0). Tests that need a
small mesh spawn a subprocess (tests/test_dryrun_small.py).

Every test phase (setup, call, teardown) runs under a ``SIGALRM`` time
limit: ``TIME_LIMIT_S`` by default, ``@pytest.mark.time_limit(seconds)``
where a test needs more. A test that runs out fails under its own name and
the session goes on. The alarm interrupts Python code only: a hang inside
one native call is not cut short.

A federation that several tests of one file compare against runs once:
through ``shared_run``, and through the ``preempt_harness`` fixture for a
whole full/killed/resumed triple. The fixtures and helpers that more than
one test file uses live here too."""

import contextlib
import dataclasses
import signal

import numpy as np
import pytest

import jax

from repro.configs.base import FedConfig
from repro.configs.registry import get_config, smoke_variant
from repro.core.state import field_dtypes
from repro.data import make_vision_data
from repro.fed import (
    AsyncConfig,
    CheckpointHook,
    FederatedSpec,
    HierarchyConfig,
    KillAtRound,
    SimulatedPreemption,
)
from repro.models import build_model

# More than twice the slowest single test (155 s, with six test files
# running at once on an 8-core CPU host).
TIME_LIMIT_S = 400


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "time_limit(seconds): fail the test when one of its "
        f"phases runs longer than this (default {TIME_LIMIT_S} s)")


@contextlib.contextmanager
def _time_limit(item, phase):
    marker = item.get_closest_marker("time_limit")
    seconds = marker.args[0] if marker else TIME_LIMIT_S

    def expire(signum, frame):
        pytest.fail(f"{item.nodeid}: {phase} ran past its time limit of "
                    f"{seconds} s")

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item, "setup"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item, "call"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    with _time_limit(item, "teardown"):
        yield


def _key(value):
    """A hashable stand-in for one argument of a federation: by value where
    it has one (configs, names, numbers, arrays, specs), by object identity
    otherwise (models, datasets, hook and executor instances)."""
    if isinstance(value, np.ndarray):
        return ("ndarray", value.dtype.str, value.shape, value.tobytes())
    if isinstance(value, (list, tuple)):
        return tuple(_key(v) for v in value)
    if isinstance(value, FederatedSpec):
        return (FederatedSpec,) + tuple(
            _key(getattr(value, f.name)) for f in dataclasses.fields(value))
    try:
        hash(value)
        return value
    except TypeError:
        return ("object", id(value))


_RUNS = {}


def shared_run(run, *args, **kwargs):
    """``run(*args, **kwargs)``, computed once per distinct arguments in a
    test file. ``run`` is a function such as ``run_federated``, or a
    ``FederatedSpec``, which runs as ``spec.build().run()``. Later calls
    return the same result object, so a test that changes it must copy it
    first."""
    key = (_key(run), _key(args), _key(sorted(kwargs.items())))
    if key not in _RUNS:
        result = (run.build().run() if isinstance(run, FederatedSpec)
                  else run(*args, **kwargs))
        # the arguments stay referenced, so no identity in a key is reused
        _RUNS[key] = (result, (run, args, kwargs))
    return _RUNS[key][0]


@pytest.fixture(autouse=True, scope="module")
def _forget_shared_runs():
    """Runs are shared within a file only: ``--dist loadfile`` puts other
    files on other workers anyway, and memory stays bounded."""
    yield
    _RUNS.clear()


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def np_rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# Federations used by more than one test file
# ---------------------------------------------------------------------------


def tiny_model():
    """The CPU test model: ResNet-18 at d_model=8 (all eight blocks)."""
    return build_model(dataclasses.replace(
        smoke_variant(get_config("resnet18-cifar10")), d_model=8))


@pytest.fixture(scope="module")
def quickstart_setup():
    """The quickstart config at 5 rounds: the golden-capture configuration
    and the hierarchy's E=1 equivalence pin."""
    fed = FedConfig(num_clients=12, participation=0.5, rounds=5,
                    local_epochs=2, local_batch=16, lr=0.3, mu=0.1,
                    dirichlet_alpha=0.1, seed=0)
    data = make_vision_data(fed, train_per_class=48, test_per_class=16, noise=0.3)
    return fed, data, tiny_model()


@pytest.fixture(scope="module")
def small_setup():
    fed = FedConfig(num_clients=6, participation=0.5, rounds=3, local_epochs=1,
                    local_batch=8, lr=0.2, mu=0.1, dirichlet_alpha=0.1, seed=0)
    data = make_vision_data(fed, train_per_class=24, test_per_class=8, noise=0.3)
    return fed, data, tiny_model()


# ---------------------------------------------------------------------------
# The {sync, async} × {flat, hierarchical} matrix of the kill/resume and
# telemetry tests
# ---------------------------------------------------------------------------

ROUNDS = 4
KILL_AT = 1  # snapshot on disk covers rounds 0..1 → resume from round 2
MATRIX = [(p, t) for p in ("sync", "async") for t in ("flat", "hierarchical")]


@pytest.fixture(scope="module")
def matrix_setup():
    fed = FedConfig(num_clients=6, participation=0.5, rounds=ROUNDS,
                    local_epochs=1, local_batch=8, lr=0.2, mu=0.1,
                    dirichlet_alpha=0.1, seed=0)
    data = make_vision_data(fed, train_per_class=24, test_per_class=8,
                            noise=0.3)
    return fed, data, tiny_model()


def make_spec_factory(setup, policy, topology, compact=False,
                      selector="heterosel"):
    """A ``make_spec(hooks, **over)`` callable for one matrix cell.

    The async cells are deliberately hostile: heterogeneous latency
    multipliers, a finite deadline, over-selection and log-normal jitter,
    so the virtual clock holds in-flight completions between rounds."""
    fed, data, model = setup
    kw = dict(selector=selector, steps_per_round=2, compact_state=compact)
    if topology == "hierarchical":
        fed = dataclasses.replace(fed, topology="hierarchical", edge_count=3)
        kw["hier_cfg"] = HierarchyConfig(edges_per_round=2)
    if policy == "async":
        fed = dataclasses.replace(fed, round_policy="async")
        kw["system"] = np.asarray([1.0, 3.0, 0.5, 2.5, 1.0, 4.0])
        kw["async_cfg"] = AsyncConfig(deadline=1.5, over_select_frac=0.5,
                                      jitter=0.1)

    def make_spec(hooks, **over):
        return FederatedSpec(model, fed, data, hooks=list(hooks),
                             **{**kw, **over})

    return make_spec


def assert_bitwise_resume(full, resumed, engine, *, compact):
    assert engine.start_round == KILL_AT + 1
    np.testing.assert_array_equal(resumed.selected_history,
                                  full.selected_history)
    # float series compare as exact bit patterns, not tolerances
    np.testing.assert_array_equal(np.asarray(resumed.accuracy),
                                  np.asarray(full.accuracy))
    np.testing.assert_array_equal(np.asarray(resumed.train_loss),
                                  np.asarray(full.train_loss))
    for name in ("wall_clock", "round_staleness", "cloud_uploads"):
        a, b = getattr(full, name), getattr(resumed, name)
        assert (a is None) == (b is None), name
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(full.params),
            jax.tree_util.tree_leaves_with_path(resumed.params)):
        assert ka == kb
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8),
                                      err_msg=str(ka))
    # the checkpoint must hand back the SoA layout it was given
    layout = field_dtypes(engine.state)
    assert layout["last_selected"] == "int32"
    assert layout["loss_prev"] == ("bfloat16" if compact else "float32")


def _preempt(spec, kill_at, phase, every, tmp_path_factory):
    full = shared_run(spec)
    ckdir = str(tmp_path_factory.mktemp("preempt"))
    with pytest.raises(SimulatedPreemption):
        dataclasses.replace(spec, hooks=[
            *spec.hooks, CheckpointHook(ckdir, every=every),
            KillAtRound(kill_at, phase=phase)]).build().run()
    engine = dataclasses.replace(spec, hooks=[
        *spec.hooks, CheckpointHook(ckdir, every=every)]).build()
    resumed = engine.run()
    return full, resumed, engine


@pytest.fixture(scope="session")
def preempt_harness(tmp_path_factory):
    """Simulated preemption: full run / killed run / resumed run.

    Returns ``run(spec, kill_at, *, phase='round_end', every=1)``. The
    harness runs ``spec`` uninterrupted (through ``shared_run``), then kills
    an identical run after round ``kill_at`` via ``KillAtRound`` (with a
    ``CheckpointHook`` saving every ``every`` rounds *before* the kill
    hook, like a real preemption landing after the save), then resumes
    from the checkpoint directory. Returns ``(full, resumed, engine)`` —
    the two FLResults plus the resumed engine (e.g. for ``start_round``).
    Each distinct ``(spec, kill_at, phase, every)`` runs once per test
    file; its checkpoint directory lives for the session. The whole resume
    test matrix builds on this instead of ad-hoc truncated-round loops."""

    def run(spec, kill_at, *, phase="round_end", every=1):
        return shared_run(_preempt, spec, kill_at, phase, every,
                         tmp_path_factory)

    return run
