import math

import numpy as np

from stats import OUT_TOKEN_WEIGHT, jain, percentile, weighted_service


def test_jain_known_values():
    assert jain([5.0, 5.0]) == 1.0
    assert jain([1.0, 0.0, 0.0, 0.0]) == 0.25
    # a 2:1 split of service: (3)^2 / (2 * 5) = 0.9
    assert math.isclose(jain([2.0, 1.0]), 0.9)
    assert jain([]) == 1.0 and jain([0.0, 0.0]) == 1.0
    assert jain([1.0, float("nan"), 1.0]) == 1.0


def test_percentile_matches_linear_interpolation():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert percentile(xs, 50) == 2.5
    assert math.isclose(percentile(xs, 95), 3.85)
    assert percentile(range(101), 95) == 95.0
    assert math.isnan(percentile([], 95))
    assert percentile(xs, 95) == float(np.percentile(xs, 95))


def test_weighted_service_bills_output_four_times():
    assert OUT_TOKEN_WEIGHT == 4.0
    assert weighted_service(240, 0) == 240.0
    assert weighted_service(10, 3) == 22.0
