"""The work count behind ``scan_roofline_share`` and the peak table."""
import numpy as np
import onchip_testkit  # noqa: F401
import pytest

import workcount


def test_union_of_overlapping_segments_is_counted_once():
    # rows 0..5; label 0 on rows 0-3, label 1 on rows 2-5, label 2 on 3
    member = np.zeros((6, 3), bool)
    member[0:4, 0] = True
    member[2:6, 1] = True
    member[3, 2] = True
    routed = [((0,), 3), ((1,), 2), ((0, 2), 1), ((0,), 1)]
    w = workcount.scan_work(member, routed, dim=4, dtype_bytes=4,
                            label_words=1, k=2)
    # segments: {0} -> rows 0-3 (4), {1} -> rows 2-5 (4), {0,2} -> row 3
    # union rows 0-5 = 6 rows at 4*4 + 4 + 4 = 24 bytes each
    # row ids of the three distinct segments: (4 + 4 + 1) * 4 bytes
    # 7 queries at 4*4 + 4 bytes, outputs 7 * 2 * 12 bytes
    assert w["bytes"] == 6 * 24 + 9 * 4 + 7 * 20 + 7 * 2 * 12
    # operations 2*D per (query, row of its segment): 4*4 + 2*4 + 1*1 rows
    assert w["ops"] == 2 * 4 * (4 * 4 + 2 * 4 + 1 * 1)


def test_empty_key_covers_every_row():
    member = np.zeros((5, 2), bool)
    w = workcount.scan_work(member, [((), 2)], dim=2, dtype_bytes=4,
                            label_words=1, k=1)
    assert w["ops"] == 2 * 2 * 5 * 2


def test_least_seconds_names_the_binding_bound():
    peak = workcount.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["bf16_flops_per_s"] == 197e12
    t, bound = workcount.least_seconds({"bytes": 819e9, "ops": 1.0}, peak)
    assert (t, bound) == (1.0, "bytes")
    t, bound = workcount.least_seconds({"bytes": 1.0, "ops": 394e12}, peak)
    assert (t, bound) == (2.0, "ops")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        workcount.peaks("TPU v9 imaginary")


def test_key_labels_reads_the_bitmask_words():
    assert workcount.key_labels((0b1010, 1)) == [1, 3, 64]
    assert workcount.key_labels((0, 0)) == []
