import numpy as np
import pytest

from stats import percentile


@pytest.mark.parametrize("n", [1, 2, 3, 10, 257, 1000])
@pytest.mark.parametrize("q", [0, 50, 95, 99, 100])
def test_pooled_percentile_matches_numpy(n, q):
    rng = np.random.default_rng(n)
    # pooled raw samples of several lanes, heavy-tailed like latencies
    lanes = [list(rng.lognormal(0.0, 1.5, size=rng.integers(0, 8))) for _ in range(n)]
    pooled = [x for lane in lanes for x in lane] or [1.0]
    assert percentile(pooled, q) == pytest.approx(np.percentile(pooled, q), rel=1e-12)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        percentile([], 50)
