"""The SSD scan's counts (flops/ssd.py) and its trace readers (op_scopes.py,
metrics/ssd_device_ms.py, metrics/ssd_roofline.py), on the CPU.

The fixtures are the two v5e traces of test_chipbench_program_spans.py:
neither holds an op under the ``mamba2.ssd`` scope, as the parent program's
traces and the ResNet cells' do not."""

import importlib
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

import op_scopes  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from flops import mamba2, ssd  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SMALL = str(FIXTURES / "v5e_small.xplane.pb")
PHASES = str(FIXTURES / "v5e_phases.xplane.pb")
CELL = "mamba2.xsilo-k16"
READERS = ["ssd_device_ms", "ssd_roofline"]


def _cfg():
    return json.loads((BENCH / "configs" / "mamba2-370m.json").read_text())


def test_ssd_counts_by_hand():
    cfg = _cfg()
    d, di, n, nh, cl, layers = 1024, 2048, 128, 32, 256, 12
    per_token = 2 * cl * n + 2 * cl * di + 2 * di * n + 2 * di * n
    assert ssd.layer_flops_per_token(cfg) == per_token == 2_162_688
    # The same scan terms as the whole layer's count, beside the projections
    # and the convolution.
    proj = 2 * d * (2 * di + 2 * n + nh) + 2 * di * d
    conv = 2 * 4 * (di + 2 * n)
    assert mamba2.layer_flops_per_token(cfg) == proj + conv + per_token
    # float32 x, dt, B, C read and y written, per token and layer.
    assert ssd.layer_bytes_per_token(cfg) == 4 * (di + nh + n + n + di) == 17_536
    cell = run.load_cell(CELL)
    tokens = 4 * 4 * 4 * 1024  # clients x steps x sequences x tokens
    assert ssd.train_flops(cfg, cell) == 3 * per_token * layers * tokens == 5_102_421_147_648
    assert ssd.train_bytes(cfg, cell) == 3 * 17_536 * layers * tokens == 41_372_614_656


def test_scoped_seconds_sums_only_scoped_ops_of_the_program():
    modules = [("jit_local_train(12)", 0, 100), ("jit_eval(3)", 100, 200)]
    scopes = {"while": "jit(local_train)/mamba2.ssd/scan/while",
              "body": "jit(local_train)/transpose(jvp(mamba2.ssd))/scan/add",
              "intra": "jit(local_train)/checkpoint/mamba2.ssd/intra/exp:",
              "proj": "jit(local_train)/dot_general:",
              "eval_scan": "jit(eval)/mamba2.ssd/intra/exp"}
    ops = [("intra", 2, 6),        # scoped, starts before the window
           ("while", 10, 30),      # scoped, holds its body
           ("body", 12, 15),       # nested: counted once
           ("proj", 30, 50),       # not scoped
           ("unnamed", 50, 60),    # no scope stat at all
           ("intra", 60, 95),      # scoped, ends after the window
           ("eval_scan", 110, 120)]  # scoped, but another program
    got = op_scopes.scoped_seconds(ops, modules, scopes, lo=4, hi=90,
                                   program="jit_local_train", scope="mamba2.ssd")
    assert got == pytest.approx(((6 - 4) + (30 - 10) + (90 - 60)) / 1e9)
    assert op_scopes.scoped_seconds(ops, modules, scopes, lo=0, hi=200,
                                    program="jit_eval", scope="mamba2.ssd") == \
        pytest.approx(10 / 1e9)


def test_op_scopes_reads_the_tf_op_stat():
    """The one op of the small fixture with a scope: a fusion of
    ``jit(<lambda>)``'s dot_general. The trimmed phases fixture keeps none."""
    small = op_scopes.op_scopes(SMALL)
    assert list(small) == [0]
    assert list(small[0].values()) == ["jit(<lambda>)/dot_general:"]
    assert next(iter(small[0])).startswith("%fusion = f32[512,512]")
    assert op_scopes.op_scopes(PHASES) == {0: {}}


def metric_context(trace):
    c = run.load_cell(CELL)
    c["name"] = CELL
    return run.MetricContext(cell=c, kind="TPU v5 lite",
                             rounds=[{"end": 1.0, "wall_s": 1.7, "select_ms": 4.0}] * 4,
                             compiles=0, trace=trace)


@pytest.mark.parametrize("fixture,n_rounds", [(SMALL, 4), (PHASES, 2)], ids=["small", "phases"])
def test_readers_silent_without_the_scope(fixture, n_rounds):
    red = trace_reduce.reduce_trace(None, "bench.round", n_rounds=n_rounds, chips=1,
                                    host_spans=["^bench\\.gather$"], path=fixture)
    for trace in (red, dict(red, xplane=fixture)):
        for name in READERS:
            assert importlib.import_module(f"metrics.{name}").read(metric_context(trace)) is None


def test_scoped_ms_on_the_small_fixture_by_hand():
    """With a scope the small fixture does carry, the device time per round
    is the union of that fusion's runs in the window over its 4 rounds."""
    red = trace_reduce.reduce_trace(None, "bench.round", n_rounds=4, chips=1,
                                    host_spans=["^bench\\.gather$"], path=SMALL)
    ctx = metric_context(dict(red, xplane=SMALL))
    raw = trace_reduce.load(SMALL)
    rounds = sorted(raw["host"]["bench.round"])
    lo, hi = rounds[0][0], rounds[3][1]
    fusion = [(s, e) for name, s, e in raw["devices"][0]["ops"] if name.startswith("%fusion =")]
    want = sum(min(e, hi) - max(s, lo) for s, e in fusion if min(e, hi) > max(s, lo))
    got = op_scopes.scoped_ms(ctx, "jit__lambda", "/dot_general")
    assert want > 0 and got == pytest.approx(want / 1e9 / 4 * 1e3)
