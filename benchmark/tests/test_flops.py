"""``flops.py`` against arithmetic done by hand for both configurations."""
import json
import os

import pytest

from benchmark import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,matmul,total", [
    # 24 * (4 * 1024^2 + 2 * 1024 * 4096) + 50257 * 1024
    ("gpt2-medium", 24 * 12_582_912 + 51_463_168, 354_551_808),
    # 36 * (4 * 1280^2 + 2 * 1280 * 5120) + 50257 * 1280
    ("gpt2-large", 36 * 19_660_800 + 64_328_960, 773_521_920),
])
def test_parameter_counts(name, matmul, total):
    cfg = config(name)
    assert flops.matmul_params(cfg) == matmul
    # + positions (1024 * d) + 2 LayerNorm scales a layer + the final one
    assert flops.total_params(cfg) == total


def test_train_flops_per_token():
    cfg = config("gpt2-medium")
    # 6 * 353,453,056 + 24 layers * 6 * 1024 * 1024
    assert flops.train_flops_per_token(cfg, 1024) == pytest.approx(
        6 * 353_453_056 + 24 * 6 * 1024 * 1024)
    assert flops.train_flops_per_token(cfg, 1024) / 1e9 == pytest.approx(
        2.2717, abs=1e-3)


def test_flash_call():
    # [4, 16, 1024, 64] float32: half square = 4*16*1024*1024*64
    f, b = flops.flash_call(4, 16, 1024, 64, 4, backward=False)
    assert f == 2 * 4 * 16 * 1024 * 1024 * 64
    assert b == 4 * (4 * 16 * 1024 * 64) * 4
    f, b = flops.flash_call(4, 16, 1024, 64, 4, backward=True)
    assert f == 5 * 4 * 16 * 1024 * 1024 * 64
    assert b == 8 * (4 * 16 * 1024 * 64) * 4


@pytest.mark.parametrize("name,layers,head,per_token", [
    ("gpt2-medium", 24 * 12_582_912 * 4, 51_463_168 * 2, 2 * 24 * 1024 * 2),
    ("gpt2-large", 36 * 19_660_800 * 4, 64_328_960 * 2, 2 * 36 * 1280 * 2),
])
def test_decode_tick_bytes(name, layers, head, per_token):
    cfg = config(name)
    assert flops.kv_bytes_per_token(cfg, 2) == per_token
    got = flops.decode_tick_bytes(cfg, layer_weight_bytes=4, table_bytes=2,
                                  kv_bytes=2, live_tokens=5000)
    assert got == layers + head + 5000 * per_token


def test_peaks_table_has_the_v5e_with_sources():
    with open(os.path.join(os.path.dirname(CONFIGS), "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s_bf16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert v5e["hbm_bytes"] == 16e9
    assert all(row["source"] for row in peaks.values())
