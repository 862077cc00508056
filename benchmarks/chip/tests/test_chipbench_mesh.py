"""A cell on several chips runs on a mesh its workload's spec names: the
harness refuses a cell whose chips and mesh disagree, and a tiny
cross-device cell on a four-device 'pod' mesh (CPU devices) runs the
sharded cohort end to end and reads correct."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parents[1] / "src"))
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


@pytest.mark.parametrize("chips,mesh", [(4, None), (1, {"pod": 4}), (4, {"pod": 2})])
def test_chips_and_mesh_must_agree(chips, mesh):
    cell = {"chips": chips, "spec": {} if mesh is None else {"mesh": mesh}}
    with pytest.raises(run.BenchError, match="mesh"):
        run.check_mesh(cell)


POD_RUN = """
import json, sys
sys.path[:0] = [{src!r}, {bench!r}, {tests!r}]
import chipbench_tiny, run
cell = chipbench_tiny.tiny_cell("resnet18.xdev-k1e6")
cell["chips"] = 4
cell["spec"]["mesh"] = {{"pod": 4}}
run.check_mesh(cell)
out = run.run_cell(cell, seed=2 ** 31 + 91, seconds=2.0, trace=False, require_tpu=False)
print(json.dumps({{"correct": out["correct"], "count": out["device"]["count"],
                  "checks": out["checks"]}}))
"""


def test_pod_mesh_cell_runs_sharded_and_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = POD_RUN.format(src=str(BENCH.parents[1] / "src"), bench=str(BENCH),
                          tests=str(BENCH / "tests"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["count"] == 4
    assert out["correct"], out["checks"]
