"""Work counts and the peak table."""

import math

import pytest

import peaks
import workcount


def test_fft_counts():
    n = 1 << 28
    assert workcount.fft_flops(n) == 5 * n * 28
    assert workcount.fft_bytes(n) == 2 * 8 * n
    assert workcount.fft_exchange_bytes(n, 1) == 0
    assert workcount.fft_exchange_bytes(n, 4) == 805_306_368


def test_fft_exchange_matches_program_ledger():
    from repro.algorithms.fft import fft_h_bytes
    for log2n, p in ((12, 2), (20, 4), (28, 4)):
        n = 1 << log2n
        assert workcount.fft_exchange_bytes(n, p) == fft_h_bytes(n, p)


def test_pagerank_bytes():
    assert workcount.pagerank_bytes(1, 16 << 20, 1 << 20) == (
        16 * (16 << 20) + 12 * (1 << 20))
    assert workcount.pagerank_bytes(18, 10, 2) == 18 * (160 + 24)


def test_least_seconds_takes_the_binding_bound():
    peak = {"flops": 100.0, "hbm_Bps": 10.0}
    assert workcount.least_seconds(1000.0, 10.0, 1, peak) == 10.0
    assert workcount.least_seconds(10.0, 1000.0, 2, peak) == 50.0
    n = 1 << 28
    v5e = peaks.lookup("TPU v5 lite")
    t = workcount.least_seconds(workcount.fft_flops(n),
                                workcount.fft_bytes(n), 1, v5e)
    assert math.isclose(t, 16 * n / 819e9)


def test_v5e_peaks_are_the_published_ones():
    for kind in ("TPU v5 lite", "TPU v5e"):
        p = peaks.lookup(kind)
        assert p["flops"] == 197e12
        assert p["hbm_Bps"] == 819e9
        assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5p", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.lookup(kind)
