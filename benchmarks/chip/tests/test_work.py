"""Work counts against sums made by hand."""
import types

import numpy as np
import pytest

import harness
import work
from peaks import PEAKS, UnknownDevice, peaks


def test_integrate_floor_is_read_x_write_y():
    assert work.integrate_floor_bytes(10, 3) == 2 * 10 * 3 * 4
    # the mesh cells: 163,842 x 3 f32, read once and written once
    assert work.integrate_floor_bytes(163842, 3) == 3_932_208


def test_fdist_matvec_work_by_hand():
    # B=2 jobs of a=3 targets, b=5 sources, d=4 channels
    flops, nbytes = work.fdist_matvec_work(2, 3, 5, 4)
    assert flops == 2 * (2 * 3 * 5 * 4) == 240
    assert nbytes == 4 * (2 * 3 + 2 * 5 + 2 * 5 * 4 + 2 * 3 * 4) == 320


def test_plan_counts_read_the_spec_buckets():
    spec = types.SimpleNamespace(
        cross_tgt_mask=(np.zeros((2, 3), bool), np.zeros((1, 7), bool)),
        cross_src_mask=(np.zeros((2, 5), bool), np.zeros((1, 4), bool)))
    assert work.cross_buckets(spec) == [(2, 3, 5), (1, 7, 4)]


def test_least_time_is_the_larger_bound():
    p = PEAKS["TPU v5 lite"]
    assert work.least_time(197e12, 0, p) == pytest.approx(1.0)
    assert work.least_time(0, 819e9, p) == pytest.approx(1.0)
    assert work.least_time(197e12, 2 * 819e9, p) == pytest.approx(2.0)


def test_vit_model_flops_by_hand():
    c = harness.config_of(harness.load_benchmark(), "topovit-b16")
    p = work.vit_matmul_params(c)
    # q, k, v, o: 4 x 768 x 768; gated MLP: 3 x 768 x 3072
    assert p["per_layer"] == 4 * 768 * 768 + 3 * 768 * 3072 == 9_437_184
    assert p["patch_proj"] == 768 * 768
    assert p["head"] == 768 * 1000
    per_image = 6 * (2 * 9_437_184 * 196 + 589_824 * 196 + 768_000)
    assert work.vit_train_flops_per_image(c) == per_image
    assert per_image == pytest.approx(22.90e9, rel=1e-3)


def test_peaks_table_is_keyed_by_device_kind():
    p = peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9)
    assert p["source"]
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")
