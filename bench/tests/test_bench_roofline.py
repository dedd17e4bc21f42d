"""The bytes the rooflines charge each kernel call, and the share."""

import pytest

from bench.lib import roofline


def test_hamming_scan_bytes():
    # (128, 1) query codes, 2,340,373 item codes of one word, and the
    # (128, 2,340,373) int32 counts
    assert roofline.hamming_scan_bytes(128, 2340373, 1) == \
        4 * (128 + 2340373 + 128 * 2340373)
    assert roofline.hamming_scan_bytes(2, 3, 2) == 4 * (4 + 6 + 6)


def test_bucket_gather_bytes():
    # cum (Q, S+1) and starts (Q, S) read, positions (Q, P) written
    assert roofline.bucket_gather_bytes(128, 65536, 32768) == \
        4 * (128 * 65537 + 128 * 65536 + 128 * 32768)
    assert roofline.bucket_gather_bytes(1, 1, 1) == 4 * (2 + 1 + 1)


def test_share_pct():
    # 819 MB in 1 ms at 819 GB/s is exactly the roofline
    assert roofline.share_pct(819e6, 1e-3, 819e9) == pytest.approx(100.0)
    assert roofline.share_pct(819e6, 4e-3, 819e9) == pytest.approx(25.0)
    with pytest.raises(ValueError):
        roofline.share_pct(1.0, 0.0, 819e9)
