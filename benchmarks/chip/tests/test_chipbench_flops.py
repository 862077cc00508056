"""Operation and byte counts of the benchmark against counts made by hand."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from flops import mamba2, resnet, score_select  # noqa: E402


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_resnet_basic_block_by_hand():
    # Stage 2's first block at 32x32 input, 64 -> 128 channels, stride 2:
    # two 3x3 convolutions and a 1x1 projection on a 16x16 output.
    conv1 = 2 * 16 * 16 * 9 * 64 * 128
    conv2 = 2 * 16 * 16 * 9 * 128 * 128
    proj = 2 * 16 * 16 * 1 * 64 * 128
    assert resnet.basic_block_flops(32, 64, 128, 2) == conv1 + conv2 + proj
    # An identity block has no projection.
    assert resnet.basic_block_flops(32, 64, 64, 1) == 2 * (2 * 32 * 32 * 9 * 64 * 64)


def test_resnet18_forward_is_1_11_gflop():
    f = resnet.forward_flops(_cfg("resnet18-cifar10"))
    assert f == 1_110_845_440
    cell = {"fed": {"num_selected": 6, "local_steps": 5, "local_batch": 32},
            "traffic": {"test_per_class": 1000, "num_classes": 10}}
    assert resnet.train_flops(_cfg("resnet18-cifar10"), cell) == 3 * f * 960
    assert resnet.eval_flops(_cfg("resnet18-cifar10"), cell) == f * 10_000


def test_mamba2_layer_by_hand():
    cfg = _cfg("mamba2-370m")
    d, di, n, nh, cl = 1024, 2048, 128, 32, 256
    proj = 2 * d * di * 2 + 2 * d * n * 2 + 2 * d * nh + 2 * di * d
    conv = 2 * 4 * (di + 2 * n)
    ssd = 2 * cl * n + 2 * cl * di + 4 * di * n
    assert mamba2.layer_flops_per_token(cfg) == proj + conv + ssd
    assert mamba2.forward_flops_per_token(cfg) == 12 * (proj + conv + ssd) + 2 * d * 50280


def test_score_select_bytes_by_hand():
    # K = 10^6 in 31 blocks of 32768 lanes: Kpad = 1,015,808.
    kpad, nblocks = 31 * 32768, 31
    operand = 9 * kpad * 2                      # bf16 state rows
    stats = operand + 128 * 4 + nblocks * 128 * 4
    select = operand + 128 * 4 + kpad * 4 + 3 * kpad * 4 + nblocks * 128 * 4
    assert score_select.kernel_bytes(10 ** 6, 2) == stats + select
    # K = 12 fits one 128-lane block.
    assert score_select.layout(12) == (128, 1, 128)
