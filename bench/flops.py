"""Operations and bytes the algorithms need, from the configuration's shapes.

Kept with the benchmark so that no change to the program moves them:
the compiled program's own count shifts with every rewrite.

* A ResNet's forward pass: every convolution and the fully connected head,
  one multiply-add per weight per output position. He et al. (2015, Table
  1) give 1.8e9 multiply-adds for ResNet-18 and 3.6e9 for ResNet-34 at 224
  px. Training counts 2 FLOPs per multiply-add and the backward pass as
  twice the forward, so 6 FLOPs per multiply-add.
* The ingest kernel: per pixel it reads one uint8 and writes one float32.
"""
from __future__ import annotations

from typing import Dict


def _out(size: int, stride: int) -> int:
    return -(-size // stride)  # "SAME" padding


def forward_macs(config: Dict) -> int:
    """Multiply-adds of one image's forward pass."""
    width, image = int(config["resnet_width"]), int(config["image_size"])
    side = _out(image, 2)  # 7x7 stem, stride 2
    macs = 7 * 7 * 3 * width * side * side
    side = _out(side, 2)  # 3x3 max-pool, stride 2
    cin = width
    for si, n in enumerate(config["resnet_blocks"]):
        cout = width * 2**si
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            side = _out(side, stride)
            macs += 3 * 3 * cin * cout * side * side  # conv1
            macs += 3 * 3 * cout * cout * side * side  # conv2
            if stride != 1 or cin != cout:
                macs += cin * cout * side * side  # 1x1 projection
            cin = cout
    return macs + cin * int(config["num_classes"])


def train_flops_per_image(config: Dict) -> float:
    return 6.0 * forward_macs(config)


def ingest_bytes_per_image(config: Dict) -> float:
    pixels = int(config["image_size"]) ** 2 * 3
    return pixels * (1 + 4)
