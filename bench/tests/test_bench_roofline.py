"""The peak table and the algorithmic cost of route_commit."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import roofline  # noqa: E402


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.roofline_share(1.0, 1.0, 1.0, "cpu")


def test_v5e_peaks_are_the_published_ones():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("C", [0, 11])
def test_cost_depends_only_on_the_algorithms_sizes(C):
    ops, nbytes = roofline.route_commit_cost(500, 10, 45, C)
    # linear in launches and arrivals, nothing else
    assert roofline.route_commit_cost(500, 20, 90, C) == (2 * ops,
                                                          2 * nbytes)
    one = roofline.route_commit_cost(500, 1, 0, C)
    per = roofline.route_commit_cost(500, 0, 1, C)
    assert ops == 10 * one[0] + 45 * per[0]
    assert nbytes == 10 * one[1] + 45 * per[1]


def test_full_scores_every_server_and_pod_its_candidates():
    full = roofline.route_commit_cost(5000, 0, 1, 0)
    pod = roofline.route_commit_cost(5000, 0, 1, 11)
    assert full[0] == 2 * 5000 + 2 and pod[0] == 2 * 11 + 2
    share, bound = roofline.roofline_share(*roofline.route_commit_cost(
        500, 1000, 4500, 11), 1e-3, "TPU v5 lite")
    assert bound == "memory" and 0 < share < 100
