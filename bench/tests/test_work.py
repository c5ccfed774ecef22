"""Work counts from shapes, against hand counts at tiny sizes."""
import pytest

from bench import work


def test_multiset_counts():
    # n=2, l=3, k=4, d=5: 2·2·3·4·5 FLOPs; V 2·5 and S 3·4·5 floats read,
    # 3 floats written
    assert work.multiset_eval(2, 3, 4, 5) == (240, (10 + 60) * 4 + 3 * 4)
    assert work.multiset_eval(2, 3, 4, 5, itemsize=2) == (
        240, (10 + 60) * 2 + 3 * 4)


def test_counts_ignore_tiles_and_lanes():
    # d=100 is counted as 100, not as the 128 lanes a kernel pads it to
    flops, _ = work.multiset_eval(50_000, 5_000, 10, 100)
    assert flops == 5 * 10**11


def test_min_seconds_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.min_seconds(1000, 50, peak) == pytest.approx(10.0)
    assert work.min_seconds(100, 500, peak) == pytest.approx(50.0)
