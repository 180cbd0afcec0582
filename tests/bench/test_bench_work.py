"""The least-bytes counts at the cells' shapes, and shares that are
never clamped."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from bench import peaks, work

AMAZON = json.load(open(os.path.join(ROOT, "bench/configs/amazon_ic.json")))


def test_commit_bytes_at_the_cells_shape():
    n, B = AMAZON["graph"]["n"], AMAZON["batch"]
    assert work.commit_bytes(B, n) == 256 * 334_863 + 8 * 334_863


def test_select_bytes_at_the_cells_shape():
    theta, n = AMAZON["theta_per_arena"], AMAZON["graph"]["n"]
    assert work.select_bytes(theta, n) == 16_384 * 334_863   # 5.49 GB


def test_share_is_not_clamped():
    bw = peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert work.share(bw, 1.0, bw) == pytest.approx(100.0)
    assert work.share(2 * bw, 1.0, bw) == pytest.approx(200.0)
    assert work.share(1.0, 0.0, bw) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
