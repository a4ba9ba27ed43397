"""The operation and byte counts against hand-worked values."""

import json
import os

from benchmark import roofline

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_topk_frame_b32_n5m_r200():
    flops, nbytes = roofline.topk_frame(config("bigann-t2i-10m"), 32)
    # 2 * 32 * 5,000,000 * 200 multiply-adds
    assert flops == 64_000_000_000
    # catalog 5,000,000 * 200 * 4 + queries 32 * 200 * 4 + replies 32 * 10 * 8
    assert nbytes == 4_000_000_000 + 25_600 + 2_560


def test_als_iter_ml20m_rank50():
    flops, nbytes = roofline.als_iter(config("als-ml20m"))
    # assembly 2 * 20e6 * (2*50^2 + 2*50) = 2.04e11;
    # solves (138,493 + 26,744) * (50^3/3 + 4*50^2) = 165,237 * 51,666.67
    assert abs(flops - (2.04e11 + 165_237 * (125_000 / 3 + 10_000))) < 1.0
    # ratings 2 * 20e6 * 8 B; each table read once and written once
    assert nbytes == 320_000_000 + 2 * 165_237 * 50 * 4


def test_peaks_name_their_source():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    assert peaks["TPU v5 lite"] == {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
