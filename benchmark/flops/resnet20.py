"""Operations of ResNet-20 (CIFAR, widths 16/32/64) from its shapes.

Multiply-accumulates of the forward pass of one 32x32x3 image, layer by
layer; the convolutions and the head only (normalization and pointwise
work are not model FLOPs). A training step is counted as three forward
passes (forward, and the two products of the backward pass), two FLOPs
to a multiply-accumulate.
"""
from __future__ import annotations

WIDTHS = (16, 32, 64)
BLOCKS_PER_STAGE = 3
IMAGE = 32
CLASSES = 10


def forward_macs_by_layer() -> list:
    """[(layer name, multiply-accumulates per image)]."""
    out = []
    side, cin = IMAGE, 3
    out.append(("stem.conv3x3", side * side * cin * WIDTHS[0] * 9))
    cin = WIDTHS[0]
    for stage, width in enumerate(WIDTHS):
        for b in range(BLOCKS_PER_STAGE):
            stride = 2 if (stage > 0 and b == 0) else 1
            side //= stride
            name = f"stage{stage + 1}.block{b}"
            out.append((name + ".conv0", side * side * cin * width * 9))
            out.append((name + ".conv1", side * side * width * width * 9))
            if stride != 1 or cin != width:
                out.append((name + ".shortcut1x1",
                            side * side * cin * width))
            cin = width
    out.append(("head.dense", cin * CLASSES))
    return out


def forward_macs_per_image() -> int:
    return sum(m for _, m in forward_macs_by_layer())


def train_flops_per_image() -> int:
    return 3 * 2 * forward_macs_per_image()
