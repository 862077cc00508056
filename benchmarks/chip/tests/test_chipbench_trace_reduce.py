"""The reduction from a profiler trace to busy time, module time and idle
gaps, on hand-made intervals and on a small trace recorded on a TPU v5e
(``fixtures/v5e_small.xplane.pb``: four ``bench.round`` spans, each a
``bench.gather`` span of 10 ms then one jitted 512x512 matmul program)."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "v5e_small.xplane.pb"
HOST_SPANS = ["^bench\\.gather$"]


def test_union_merges_overlaps_and_touching():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12)]) == [(0, 4), (5, 7), (10, 12)]


def test_gaps_fill_the_window():
    busy = [(2, 4), (6, 7)]
    assert trace_reduce.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert trace_reduce.gaps([], 0, 3) == [(0, 3)]


def test_clip():
    assert trace_reduce.clip((0, 10), 2, 5) == (2, 5)
    assert trace_reduce.clip((0, 1), 2, 5) is None


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(None, "bench.round", n_rounds=4, chips=1,
                                     host_spans=HOST_SPANS, path=str(FIXTURE))


def test_fixture_window_and_busy(reduced):
    # Four rounds of >= 15 ms of host sleeps each.
    assert 0.06 <= reduced["window_s"] <= 0.5
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["n_rounds"] == 4


def test_fixture_module_time(reduced):
    mods = reduced["modules"]
    assert "jit__lambda" in mods
    # The matmul program's device time is what a reader matching its module
    # sums; its fused matmul ops, matched by label, are a part of the busy time.
    import run

    ctx = run.MetricContext(cell={}, kind="TPU v5 lite", rounds=[], compiles=0, trace=reduced)
    assert ctx.module_seconds("^jit__lambda$") == pytest.approx(mods["jit__lambda"])
    assert 0 < mods["jit__lambda"] < reduced["window_s"]
    assert 0 < ctx.op_seconds("^jit__lambda %fusion") <= reduced["busy_s"]
    assert ctx.module_seconds("^no_such_program$") == 0
    assert any(name.startswith("jit__lambda %fusion") for name, _ in reduced["breakdown"]["device_ops"])


def test_fixture_idle_gaps(reduced):
    idle = reduced["idle"]
    total_idle = sum(idle.values())
    assert total_idle == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    # Most idle time is the host's gather span (10 ms sleeps + the copy).
    assert max(idle, key=idle.get) == "bench.gather"
    assert len(reduced["breakdown"]["idle_gaps"]) <= 10
    assert len(reduced["breakdown"]["device_ops"]) <= 10
