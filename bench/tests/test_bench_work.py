"""The lower-bound work count and the peak table."""
import json

import pytest

from bench.lib import peaks, spec

work = spec.work("blockfp_qr")


def test_work_2x2_by_hand():
    # one rotation (row 1 into row 0 at column 0): the R part rotates the
    # pairs of columns 0 and 1; with Q, two more pairs of [Q^T]
    assert work.rotations(2, 2) == 1
    assert work.pairs(2, 2, False) == 2
    assert work.pairs(2, 2, True) == 2 + 2
    iters = 23
    assert work.ops(2, 2, True, iters) == 4 * (4 * iters + 2)
    assert work.bytes_moved(2, 2, True) == 2 * 2 * 4 * 4     # [A | I]: 2 x 4
    assert work.bytes_moved(2, 2, False) == 2 * 2 * 2 * 4


def test_work_4x4_by_hand():
    # column-major schedule: (piv, tgt, col) and the R-part pairs n - col
    steps = [(0, 1, 0), (0, 2, 0), (0, 3, 0), (1, 2, 1), (1, 3, 1), (2, 3, 2)]
    r_pairs = [4, 4, 4, 3, 3, 2]
    assert work.rotations(4, 4) == len(steps)
    assert work.pairs(4, 4, False) == sum(r_pairs) == 20
    assert work.pairs(4, 4, True) == 20 + 2 * len(steps) == 32
    assert work.ops(4, 4, True, 24) == 32 * (4 * 24 + 2)
    assert work.bytes_moved(4, 4, True) == 2 * 4 * 8 * 4


def test_work_tall():
    # 4x2 without Q: columns 0 and 1 have 3 and 2 subdiagonal entries
    assert work.rotations(4, 2) == 5
    assert work.pairs(4, 2, False) == 3 * 2 + 2 * 1


@pytest.mark.parametrize("m,n", [(2, 2), (4, 4), (8, 8), (16, 8), (64, 32)])
def test_work_below_the_kernels_pairs(m, n):
    # the kernel rotates every step at the full width n + m
    kernel_pairs = work.rotations(m, n) * (n + m)
    assert work.pairs(m, n, True) <= kernel_pairs


def test_peaks_unknown_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_peaks_have_sources():
    table = json.loads((spec.BENCH / "peaks.json").read_text())["devices"]
    assert table
    for kind, entries in table.items():
        for name, entry in entries.items():
            assert entry["value"] > 0, (kind, name)
            assert entry.get("source") or entry.get("derivation"), (kind, name)
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    # clock x lanes x slots, as the derivation states
    assert v5e["int32_vector_ops_per_s"] == pytest.approx(
        197e12 / (4 * 128 * 128 * 2) * 1024 * 4, rel=1e-3)
