"""The spreads a bound is set from, reckoned as the driver's check reckons
them, on cases worked by hand."""

import statistics

import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts benchmark/ on sys.path
from benchlib import spread  # noqa: E402

SET_A = [1.00, 1.01, 1.02, 1.03, 1.04, 1.10]
SET_B = [1.00, 1.00, 1.02, 1.02, 1.04, 1.04]


def test_spread_is_pythons_quartiles_over_the_median():
    # quantiles(n=4), exclusive: positions 1.75 and 5.25 of six sorted runs
    q1, q3 = 1.00 + 0.75 * 0.01, 1.04 + 0.25 * 0.06
    assert spread.spread(SET_A) == pytest.approx((q3 - q1) / 1.025)
    # numpy's quartiles lie closer together: not the check's
    inclusive = statistics.quantiles(SET_A, n=4, method="inclusive")
    assert spread.spread(SET_A) > (inclusive[2] - inclusive[0]) / 1.025


def test_the_farthest_run_is_left_out_once():
    assert spread.without_farthest(SET_A) == [1.00, 1.01, 1.02, 1.03, 1.04]
    assert spread.without_farthest([3.0, 1.0, 1.1, 1.2]) == [1.0, 1.1, 1.2]
    # two far-off runs: one stays, and widens the spread
    two_far = [1.00, 1.01, 1.02, 1.03, 1.10, 1.10]
    assert max(spread.without_farthest(two_far)) == 1.10


def test_of_sets_reads_tight_and_loose_as_the_check_does():
    got = spread.of_sets([SET_A, SET_B])
    assert got["median"] == pytest.approx(statistics.median(SET_A + SET_B))
    assert got["set_medians"] == [pytest.approx(1.025), pytest.approx(1.02)]
    tight = [spread.spread(spread.without_farthest(s)) for s in (SET_A, SET_B)]
    assert got["tight"] == pytest.approx(sum(tight) / 2)
    assert got["loose"] == pytest.approx(max(spread.spread(SET_A),
                                             spread.spread(SET_B)))
    assert got["range"] == pytest.approx(0.10 / 1.025)
    assert got["trimmed_range"] == pytest.approx(
        (0.04 / 1.02 + (1.04 - 1.00) / 1.02) / 2)
    assert got["tight"] < got["loose"]


def test_bound_window_is_twice_the_tightest_to_eight_times_the_loosest():
    cells = {"steady": {"tight": 0.001, "loose": 0.002},
             "swinging": {"tight": 0.012, "loose": 0.020}}
    window = spread.bound_window(cells)
    assert window == {"lowest": pytest.approx(0.024), "highest": pytest.approx(0.16)}
    # a bound of 1% is never too loose, and none is set under it
    steady = spread.bound_window({"steady": cells["steady"]})
    assert steady == {"lowest": 0.01, "highest": pytest.approx(0.016)}
    assert spread.bound_window({"still": {"tight": 0.0001, "loose": 0.0002}}) \
        == {"lowest": 0.01, "highest": 0.01}
