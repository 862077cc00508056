"""The round-phase helper (``repro.obs.phases``): profiler span names,
nesting, the ``ctx.*_ms`` it sets, its Chrome-trace spans, and that it adds
no host sync to a round."""

import types
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.data import make_vision_data
from repro.fed import AsyncConfig, FederatedSpec
from repro.fed.engine import RoundHook
from repro.obs.phases import current_recorder, phase, round_step
from repro.obs.trace import NULL_TRACER, PID_WALL, TraceRecorder, validate_trace_events


class Annotations:
    """Stands in for ``jax.profiler``'s annotations and logs enter/exit."""

    def __init__(self):
        self.log = []

    def annotation(self, name, **kw):
        log = self.log

        class Ann:
            def __enter__(self):
                log.append(("enter", name, kw))
                return self

            def __exit__(self, *exc):
                log.append(("exit", name, kw))
                return False

        return Ann()


@pytest.fixture
def profiler(monkeypatch):
    ann = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.annotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", ann.annotation)
    return ann


def test_profiler_names_and_nesting(profiler):
    rec = TraceRecorder()
    with round_step(3):
        with phase("execute", recorder=rec):
            assert current_recorder() is rec
            with phase("gather", recorder=current_recorder()):
                pass
            with phase("aggregate"):
                assert current_recorder() is NULL_TRACER
    assert current_recorder() is NULL_TRACER
    assert [(k, n) for k, n, _ in profiler.log] == [
        ("enter", "fed.round"), ("enter", "fed.execute"),
        ("enter", "fed.gather"), ("exit", "fed.gather"),
        ("enter", "fed.aggregate"), ("exit", "fed.aggregate"),
        ("exit", "fed.execute"), ("exit", "fed.round")]
    assert profiler.log[0][2] == {"step_num": 3}


def test_ctx_ms_and_chrome_spans(profiler):
    rec = TraceRecorder()
    ctx = types.SimpleNamespace(select_ms=0.0, execute_ms=0.0)
    with phase("select", ctx, rec, args={"round": 0}):
        pass
    with phase("execute", ctx, rec, args={"round": 0}) as ex:
        with phase("gather", recorder=current_recorder()):
            pass
        ex.args["cohort"] = 2
    assert ctx.select_ms > 0 and ctx.execute_ms > 0
    assert not hasattr(ctx, "gather_ms")  # no ctx given: nothing set
    validate_trace_events(rec.events)
    spans = [e for e in rec.events if e["ph"] == "B"]
    assert [e["name"] for e in spans] == ["select", "gather", "execute"]
    tracks = {e["args"]["name"]: e["tid"] for e in rec.events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    # top-level phases on "round", a nested one on its parent's track
    assert spans[0]["tid"] == spans[2]["tid"] == tracks["round"]
    assert spans[1]["tid"] == tracks["execute"]
    assert spans[2]["args"] == {"round": 0, "cohort": 2}


def test_failed_phase_records_nothing(profiler):
    rec = TraceRecorder()
    ctx = types.SimpleNamespace(select_ms=-1.0)
    with pytest.raises(KeyError):
        with phase("select", ctx, rec):
            raise KeyError("x")
    assert ctx.select_ms == -1.0 and rec.events == []
    assert current_recorder() is NULL_TRACER
    assert profiler.log[-1][:2] == ("exit", "fed.select")


class Fetches:
    """A device value that counts every way a host could fetch it."""

    def __init__(self):
        self.calls = Counter()

    def __getattr__(self, name):
        if name in ("block_until_ready", "item", "tolist"):
            self.calls[name] += 1
            return lambda *a, **k: None
        raise AttributeError(name)

    def __array__(self, *a, **k):
        self.calls["__array__"] += 1
        return np.zeros(())

    def __float__(self):
        self.calls["__float__"] += 1
        return 0.0


def test_phase_touches_nothing_it_is_given(profiler):
    val = Fetches()
    rec = TraceRecorder()
    ctx = types.SimpleNamespace(select_ms=0.0, value=val)
    with phase("select", ctx, rec, args={"round": 1, "value": val}):
        pass
    with phase("aggregate", recorder=rec, args={"round": 1}) as p:
        p.args["value"] = val
    assert val.calls == Counter()


class LinearClassifier:
    """Softmax regression on flattened images: a model the engine trains and
    evaluates (one compiled program, as ``default_eval`` runs every model) in
    well under a second."""

    cfg = types.SimpleNamespace(family="resnet")

    def init_params(self, key):
        return {"w": 0.01 * jax.random.normal(key, (8 * 8 * 3, 10))}

    def forward(self, params, batch):
        x = batch["images"].reshape(batch["images"].shape[0], -1)
        return x @ params["w"]

    def loss(self, params, batch):
        logp = jax.nn.log_softmax(self.forward(params, batch))
        return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], 1))


# Host fetches of device arrays: the ways a round can wait for the device
# (``np.asarray`` goes through ``__buffer__`` where the array lives in host
# memory, else ``__array__``).
FETCHES = ("__array__", "__buffer__", "__float__", "__int__", "__bool__",
           "__index__", "item", "tolist", "block_until_ready")


def test_round_syncs_unchanged(monkeypatch):
    """A flat sync round fetches from the device exactly as before the
    phases were marked: the cohort mask (select), the dense loss and
    update-norm observations (state update), the eval metric."""
    from jax._src.array import ArrayImpl

    calls = Counter()
    for name in FETCHES:
        orig = getattr(ArrayImpl, name)

        def counted(self, *a, _orig=orig, _name=name, **k):
            calls[_name] += 1
            return _orig(self, *a, **k)

        monkeypatch.setattr(ArrayImpl, name, counted)

    per_round = []

    class Snap(RoundHook):
        def on_round_start(self, ctx):
            self.before = Counter(calls)

        def on_round_end(self, ctx):
            per_round.append(+(calls - self.before))

    model = LinearClassifier()
    fed = FedConfig(num_clients=4, participation=0.5, rounds=2, local_epochs=1,
                    local_batch=4, lr=0.05, mu=0.1, dirichlet_alpha=0.1, seed=0)
    data = make_vision_data(fed, image_size=8, train_per_class=8, test_per_class=4)
    eng = FederatedSpec(model, fed, data, selector="heterosel", steps_per_round=1,
                        hooks=[Snap()]).build()
    eng.tracer = TraceRecorder()  # an enabled recorder adds no fetch either
    eng.run()
    fetched = [{"arrays": c["__array__"] + c["__buffer__"],
                "scalars": c["__float__"], "other": sum(c.values()) - c["__array__"]
                - c["__buffer__"] - c["__float__"]} for c in per_round]
    assert fetched == [{"arrays": 3, "scalars": 1, "other": 0}] * 2
    names = [e["name"] for e in eng.tracer.events
             if e["pid"] == PID_WALL and e["ph"] == "B"]
    # a nested phase is emitted when it closes, before its parent
    assert names == ["select", "gather", "execute", "aggregate", "state_update",
                     "eval"] * 2


ROUND_PHASES = ["select", "execute", "aggregate", "state_update", "eval"]


@pytest.mark.parametrize("policy", ["sync", "async"])
@pytest.mark.parametrize("topology", ["flat", "hierarchical"])
def test_one_round_skeleton_per_regime(profiler, policy, topology):
    """Every regime runs the one round: select, execute, aggregate,
    state_update and eval, once each and in that order; a hierarchical
    round runs its outer and inner selection inside select."""
    hier = topology == "hierarchical"
    fed = FedConfig(num_clients=6, participation=0.5, rounds=3, local_epochs=1,
                    local_batch=4, lr=0.05, mu=0.1, dirichlet_alpha=0.1, seed=0,
                    round_policy=policy, topology=topology,
                    edge_count=2 if hier else 0)
    data = make_vision_data(fed, image_size=8, train_per_class=8, test_per_class=4)
    kw = {}
    if policy == "async":  # stragglers that carry over between rounds
        kw = dict(system=np.asarray([1.0, 3.0, 0.5, 2.5, 1.0, 4.0]),
                  async_cfg=AsyncConfig(deadline=1.5, over_select_frac=0.5))
    eng = FederatedSpec(LinearClassifier(), fed, data, selector="heterosel",
                        steps_per_round=1, **kw).build()
    rec = eng.tracer = TraceRecorder()
    eng.run()

    tracks = {e["args"]["name"]: e["tid"] for e in rec.events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    wall = [e for e in rec.events if e["pid"] == PID_WALL and e["ph"] in "BE"]
    spans = list(zip(wall[::2], wall[1::2]))  # each span is a B, E pair
    assert all(b["name"] == e["name"] for b, e in spans)
    top = [(b["args"]["round"], b["name"]) for b, _ in spans
           if b["tid"] == tracks["round"]]
    assert top == [(t, name) for t in range(fed.rounds) for name in ROUND_PHASES]

    # the profiler's names, which the chip benchmark's readers key on
    children, stack = Counter(), []
    for kind, name, _ in profiler.log:
        if kind == "enter":
            children[(stack[-1] if stack else None, name)] += 1
            stack.append(name)
        else:
            assert stack.pop() == name
    for name in ROUND_PHASES:
        assert children[("fed.round", f"fed.{name}")] == fed.rounds
    stages = [("fed.select", "fed.outer_select"), ("fed.select", "fed.inner_select")]
    assert [children[s] for s in stages] == [fed.rounds if hier else 0] * 2
    if hier:
        select = {b["args"]["round"]: (b["ts"], e["ts"]) for b, e in spans
                  if b["name"] == "select"}
        for b, e in spans:
            if b["name"] in ("outer_select", "inner_select"):
                lo, hi = select[b["args"]["round"]]
                assert b["tid"] == tracks["select"]
                assert lo <= b["ts"] <= e["ts"] <= hi
