import json
import os

import pytest

from bench.flops import forward_macs, ingest_bytes_per_image, train_flops_per_image

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("blocks,published", [((2, 2, 2, 2), 1.8e9), ((3, 4, 6, 3), 3.6e9)])
def test_forward_macs_match_he_et_al(blocks, published):
    # He et al. 2015, Table 1: multiply-adds of one 224 px image's forward
    # pass, ResNet-18 and ResNet-34 (the same widths, more blocks)
    macs = forward_macs(dict(_config("resnet18"), resnet_blocks=list(blocks)))
    assert abs(macs - published) / published < 0.03, macs


def test_training_counts_backward_as_twice_forward():
    cfg = _config("resnet18")
    assert train_flops_per_image(cfg) == 6 * forward_macs(cfg)


def test_ingest_bytes_are_u8_in_f32_out():
    assert ingest_bytes_per_image(_config("resnet18")) == 224 * 224 * 3 * 5
