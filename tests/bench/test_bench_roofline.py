"""Least bytes against hand counts (``bench/roofline.py``)."""

import numpy as np
import pytest

from bench import roofline


@pytest.mark.parametrize("ids, want", [
    ([], 0),
    ([7], 2),                                 # array of one: 2 bytes
    ([1, 5, 9], 6),                           # array of three
    ([0, 1, 2, 3], 4),                        # one run: 4 bytes < 8 array
    (list(range(10, 20)) + [30], 8),          # two runs (8) < array (22)
    (list(range(0, 65536, 2)), 8192),         # 32,768 values: a bitmap
    (list(range(65536)), 4),                  # a full chunk is one run
    ([3, 65536 + 3], 4),                      # two chunks, an array each
    ([65535, 65536], 4),                      # a run does not span chunks
])
def test_sorted_ids_bytes_hand_counts(ids, want):
    assert roofline.sorted_ids_bytes(np.asarray(ids, np.int64)) == want


def test_mask_bytes_equals_sorted_ids_bytes():
    rng = np.random.default_rng(3)
    mask = rng.random(3 * 65536 + 17) < 0.03
    mask[70000:80000] = True
    assert roofline.mask_bytes(mask) == \
        roofline.sorted_ids_bytes(np.flatnonzero(mask))


def test_container_bytes_takes_the_smallest_encoding():
    got = roofline.container_bytes([0, 1, 5000, 5000, 100], [0, 1, 2500, 1, 100])
    assert got.tolist() == [0, 2, 8192, 4, 200]


def test_the_peak_table_is_keyed_by_device_kind():
    from bench import peaks

    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
