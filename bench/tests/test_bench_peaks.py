"""The peaks table and the arena's least-work arithmetic."""

import pytest

from bench import peaks


def test_v5e_peaks_and_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("dim", [1, 2, 3, 4, 5, 6])
@pytest.mark.parametrize("n", [32, 40])
def test_predict_and_update_work(dim, n):
    # one agent: an (n, dim + 1) float32 weight block
    w = 4 * n * (dim + 1)
    assert peaks.predict_work(n, dim) == (w, 2 * n * (dim + 1))
    nbytes, ops = peaks.update_work(n, dim)
    # read w, g2, x, costs; write w, g2
    assert nbytes == 2 * w + 4 * dim + 4 * n + 2 * w
    assert ops == 10 * n * (dim + 1) + n


def test_least_seconds_takes_the_binding_bound():
    p = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert peaks.least_seconds(2e9, 1e9, p) == 2.0
    assert peaks.least_seconds(1e6, 5e12, p) == 5.0
