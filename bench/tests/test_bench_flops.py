"""FLOP and byte functions, FLOPs per token and the peak table, against
values worked out by hand."""
import json
import os

import pytest

import flops
from peaks import peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,params,per_token", [
    # 33 x (4 x 1280^2 + 4 x 1280 + 2 x 1280 x 5120 + 5120 + 1280
    #       + 4 x 1280) + 2 x 1280 + 33 x 1280
    ("esm2-650m", 649_400_320, 6 * 649_400_320 + 12 * 33 * 1280 * 1024),
    # 36 x (4 x 2560^2 + 4 x 2560 + 2 x 2560 x 10240 + 10240 + 2560
    #       + 4 x 2560) + 2 x 2560 + 33 x 2560
    ("esm2-3b", 2_832_442_880, 6 * 2_832_442_880 + 12 * 36 * 2560 * 1024),
])
def test_params_and_flops_per_token(name, params, per_token):
    c = config(name)
    assert flops.param_count(c) == params
    assert flops.train_flops_per_token(c, 1024) == per_token


def test_flops_per_token_values():
    # 4.415 and 18.13 GFLOP per token
    assert flops.train_flops_per_token(config("esm2-650m"), 1024) == pytest.approx(4.4155e9, rel=1e-4)
    assert flops.train_flops_per_token(config("esm2-3b"), 1024) == pytest.approx(1.8127e10, rel=1e-4)


def test_flash_attention_work():
    # 650M, one chip: 8 rows, 20 heads, 1024, 64
    fwd = flops.flash_attention_fwd(8, 20, 1024, 64)
    assert fwd["flops"] == 4 * 8 * 20 * 1024 ** 2 * 64 == 42_949_672_960
    assert fwd["bytes"] == 4 * 8 * 1024 * 20 * 64 * 2 + 8 * 20 * 1024 * 4
    bwd = flops.flash_attention_bwd(8, 20, 1024, 64)
    assert bwd["flops"] == 2 * fwd["flops"]
    assert bwd["bytes"] == 8 * 8 * 1024 * 20 * 64 * 2 + 8 * 20 * 1024 * 4
    peak = {"flops": 197e12, "bytes": 819e9}
    # compute-bound: 42.9 GFLOP / 197 TFLOP/s = 218 us > 6.0 MB / 819 GB/s
    assert flops.least_seconds(fwd, peak) == pytest.approx(42_949_672_960 / 197e12)


def test_least_seconds_memory_bound():
    assert flops.least_seconds({"flops": 1.0, "bytes": 819e9},
                               {"flops": 197e12, "bytes": 819e9}) == 1.0


def test_peaks_table():
    assert peaks("TPU v5 lite") == {"flops": 197e12, "bytes": 819e9}
    with pytest.raises(KeyError, match="no peak rates"):
        peaks("TPU v9 imaginary")
