"""Operations and bytes of the CIFAR ResNet-18, computed from its shapes.

Model FLOPs count the multiply-adds of the convolutions and the head (two
operations each); normalization, activations and the optimizer's
elementwise work are left out, so the count is a floor on what the chip
executes. Training is forward plus backward, three times the forward.
"""

from __future__ import annotations

from typing import Any, Dict

STAGE_STRIDES = ((1, 1), (2, 1), (2, 1), (2, 1))


def conv_flops(hw_out: int, k: int, cin: int, cout: int) -> int:
    """One image through a k x k convolution with a square hw_out output."""
    return 2 * hw_out * hw_out * k * k * cin * cout


def basic_block_flops(hw_in: int, cin: int, cout: int, stride: int) -> int:
    """conv3x3(stride) + conv3x3, plus the 1x1 projection where the shape changes."""
    hw = hw_in // stride
    f = conv_flops(hw, 3, cin, cout) + conv_flops(hw, 3, cout, cout)
    if stride != 1 or cin != cout:
        f += conv_flops(hw, 1, cin, cout)
    return f


def forward_flops(cfg: Dict[str, Any]) -> int:
    """One image's forward pass."""
    w, hw = cfg["d_model"], cfg["image_size"]
    f = conv_flops(hw, 3, cfg["channels"], w)
    cin = w
    for stage, strides in enumerate(STAGE_STRIDES):
        cout = w * 2 ** stage
        for s in strides:
            f += basic_block_flops(hw, cin, cout, s)
            hw //= s
            cin = cout
    return f + 2 * cin * cfg["num_classes"]


def images_per_round(cell: Dict[str, Any]) -> int:
    fed = cell["fed"]
    return fed["num_selected"] * fed["local_steps"] * fed["local_batch"]


def eval_images(cell: Dict[str, Any]) -> int:
    t = cell["traffic"]
    return t["test_per_class"] * t["num_classes"]


def train_flops(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    """The cohort's forward and backward passes in one round."""
    return 3 * forward_flops(cfg) * images_per_round(cell)


def eval_flops(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    return forward_flops(cfg) * eval_images(cell)


def train_bytes(cfg: Dict[str, Any], cell: Dict[str, Any]) -> int:
    """A floor on the cohort program's memory traffic in one round: each
    client step reads and writes the float32 weights once and reads its
    batch of float32 images."""
    fed = cell["fed"]
    steps = fed["num_selected"] * fed["local_steps"]
    image = cfg["image_size"] ** 2 * cfg["channels"] * 4
    return steps * (2 * 4 * cfg["params"] + fed["local_batch"] * image)
