"""Cell mamba2.xsilo-k16 at a tiny size on the CPU: the sound run reads correct,
each planted fault (see chipbench_faults) reads not correct."""

import pytest

from chipbench_faults import faults_for, run_with_fault


@pytest.mark.parametrize("fault", faults_for("mamba2.xsilo-k16"))
def test_fault_decides_correct(fault, monkeypatch):
    out = run_with_fault("mamba2.xsilo-k16", fault, monkeypatch)
    assert out["correct"] == (fault == "none"), out["checks"]
