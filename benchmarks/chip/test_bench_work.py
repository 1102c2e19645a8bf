"""Work counts and the table of peaks, against numbers worked by hand."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import work  # noqa: E402

INTERNLM2 = json.loads((HERE / "configs/internlm2-1.8b.json").read_text())
MINICPM3 = json.loads((HERE / "configs/minicpm3-4b.json").read_text())
PEAKS = json.loads((HERE / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def test_internlm2_counts():
    # attention: q 2048x2048, k and v 2048x1024 each, o 2048x2048
    assert work.layer_matmul_params(INTERNLM2) == 12_582_912 + 50_331_648
    assert work.body_matmul_params(INTERNLM2) == 24 * 62_914_560
    assert work.head_params(INTERNLM2) == 2048 * 92544
    assert work.weight_bytes(INTERNLM2) == 3_398_959_104
    assert work.kv_bytes_per_token(INTERNLM2) == 96 * 1024  # 24 x 2 x 8 x 128 x 2 B
    assert work.attn_flops_per_pair(INTERNLM2) == 2 * 256 * 16 * 24


def test_minicpm3_counts():
    # wq_a 2560x768, wq_b 768x(40x96), wkv_a 2560x288, wkv_b 256x(40x128), wo 2560x2560
    assert work.layer_matmul_params(MINICPM3) == 13_516_800 + 49_152_000
    assert work.body_matmul_params(MINICPM3) == 21 * 62_668_800
    assert work.kv_bytes_per_token(MINICPM3) == 21 * 288 * 2  # latent + rope key
    assert work.attn_flops_per_pair(MINICPM3) == 2 * (96 + 64) * 40 * 21


def test_tick_work_hand_worked():
    # one decoding slot at position 100 and one prefill chunk of 64 from 0
    flops, nbytes = work.tick_work(INTERNLM2, [(1, 100), (64, 0)])
    assert flops == 197_480_349_696
    assert nbytes == 3_415_445_504
    flops, nbytes = work.tick_work(MINICPM3, [(1, 8000)])
    assert flops == 5_158_812_160
    assert nbytes == 3_104_928_576
    assert work.tick_work(INTERNLM2, []) == (0.0, 0.0)


def test_least_time_names_its_bound():
    t, bound = work.least_time_s(1e12, 1e6, PEAKS)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)
    t, bound = work.least_time_s(1e9, 3.4e9, PEAKS)
    assert bound == "memory" and t == pytest.approx(3.4e9 / 819e9)


def test_unknown_device_kind_raises():
    import harness

    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks for device kind"):
        harness.peaks_for("TPU v9 imaginary")
