"""Operation counts against hand counts, and the table of peaks."""
import json
import os

import numpy as np
import pytest

from bench import flops, models
from bench.models import _cnn

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_alexnet_forward_macs_by_hand():
    # 32x32: conv0 27*64; pool -> 16x16: conv1 576*192; pool -> 8x8:
    # conv2 1728*384, conv3 3456*256, conv4 2304*256; pool -> 4x4:
    # fc0 4096*1024, fc1 1024*512, head 512*10
    hand = (1024 * 27 * 64 + 256 * 576 * 192 + 64 * 1728 * 384
            + 64 * 3456 * 256 + 64 * 2304 * 256 + 4096 * 1024 + 1024 * 512
            + 512 * 10)
    assert hand == 171_643_904
    assert _cnn.forward_macs(_cfg("alexnet-cifar10")) == hand
    assert models.load("alexnet").forward_ops(_cfg("alexnet-cifar10"),
                                              {}) == 2 * hand


def test_resnet18_forward_macs_by_hand():
    stage0 = 4 * 1024 * 576 * 64
    later = 0
    for side, cin, w in ((16, 64, 128), (8, 128, 256), (4, 256, 512)):
        pos = side * side
        later += pos * 9 * cin * w + 3 * pos * 9 * w * w + pos * cin * w
    hand = 1024 * 27 * 64 + stage0 + later + 512 * 100
    assert hand == 555_468_800
    assert _cnn.forward_macs(_cfg("resnet18-cifar100")) == hand


def test_train_ops_full_masks():
    cfg = _cfg("alexnet-cifar10")
    mod = models.load("alexnet")
    conv0 = 1024 * 27 * 64
    assert mod.train_ops(cfg, {}) == 6 * _cnn.forward_macs(cfg) - 2 * conv0
    full = {k: np.ones(shape) for k, shape in mod.mask_units(cfg).items()}
    assert mod.train_ops(cfg, {}, full) == mod.train_ops(cfg, {})


def test_sub_model_counts_alive_inputs_and_outputs():
    cfg = _cfg("alexnet-cifar10")
    alive = {"conv0": 32, "conv1": 96, "conv2": 384, "conv3": 256,
             "conv4": 128, "fc0": 256, "fc1": 512}
    macs = _cnn.forward_macs(cfg, alive)
    hand = (1024 * 27 * 32 + 256 * 9 * 32 * 96 + 64 * 9 * 96 * 384
            + 64 * 3456 * 256 + 64 * 2304 * 128 + 16 * 128 * 256
            + 256 * 512 + 512 * 10)
    assert macs == hand


def test_masked_matmul_cost_and_alive_blocks():
    ops, nbytes = flops.masked_matmul_cost(32, 4096, 384)
    assert ops == 3 * 2 * 32 * 4096 * 384
    assert nbytes == 4 * 3 * (32 * 4096 + 4096 * 384 + 32 * 384)
    mask = np.zeros(1024)
    mask[128:256] = 1
    mask[700] = 1
    assert flops.alive_block_units(mask, 128) == 256


def test_peaks_known_and_unknown():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("source")
