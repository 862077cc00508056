"""The harness runs only on a TPU: elsewhere it fails and prints no result."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def test_cpu_is_refused(capsys):
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    rc = run.main(["--workload", "resnet18.paper-k12", "--seed", "3000000019",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in out.err and "cpu" in out.err
    assert out.out.strip() == ""


def test_too_few_chips_is_refused():
    with pytest.raises(run.BenchError, match="asks for 4 chips"):
        run.check_device(4, require_tpu=False)


def test_unknown_workload_is_refused(capsys):
    rc = run.main(["--workload", "no-such-cell", "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
