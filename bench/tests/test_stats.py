import numpy as np
import pytest

import stats


@pytest.mark.parametrize("n", [1, 2, 7, 240, 1001])
def test_percentile_is_taken_over_every_sample(n):
    xs = np.random.default_rng(n).exponential(size=n)
    for q in (50, 95, 99):
        assert stats.percentile(xs.tolist(), q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)


def test_empty_inputs_read_nothing():
    assert stats.percentile([], 95) is None and stats.mean([]) is None
