"""Cell resnet18.paper-k12 at a tiny size on the CPU: the sound run reads correct,
each planted fault (see chipbench_faults) reads not correct."""

import pytest

from chipbench_faults import faults_for, run_with_fault


@pytest.mark.parametrize("fault", faults_for("resnet18.paper-k12"))
def test_fault_decides_correct(fault, monkeypatch):
    out = run_with_fault("resnet18.paper-k12", fault, monkeypatch)
    assert out["correct"] == (fault == "none"), out["checks"]
