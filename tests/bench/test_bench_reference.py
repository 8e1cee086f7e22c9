"""The float64 reference's readings, by hand."""
import numpy as np
import pytest

K = np.array([[2.0, 4.0], [8.0, 16.0], [1.0, 1.0], [50.0, 100.0]])


def _judge(*decisions):
    from bench.reference.check import judge, sparse

    return judge(K, [(np.asarray(d, float), *sparse(x))
                     for d, x in decisions])


def test_exact_cover_reads_zero_shortfall_and_no_removable_node():
    # one node of type 1 gives (4, 16, 1, 100): exactly the demand
    r = _judge(([4, 16, 1, 100], [0, 1]))
    assert r["shortfall_raw"] == 0.0
    assert r["removable_share"] == 0.0
    assert r["bad_counts"] == 0 and r["decisions"] == 1


def test_short_allocation_reads_its_shortfall():
    r = _judge(([4, 16, 1, 100], [1, 0]))     # (2, 8, 1, 50)
    assert r["shortfall_raw"] == pytest.approx(50.0)


def test_one_node_too_many_reads_the_slack_left():
    # two nodes of type 0 cover (4, 16, 2, 100) of (3, 12, 1, 80): taking
    # one away leaves (2, 8, 1, 50), short; three leave a removable node
    r = _judge(([3, 12, 1, 80], [2, 0]))
    assert r["removable_share"] == 0.0
    r = _judge(([3, 12, 1, 80], [3, 0]))
    assert r["removable_share"] == 1.0


def test_removable_share_is_a_share_of_the_allocations():
    # a node that covers the demand exactly once removed still counts
    r = _judge(([3, 12, 1, 80], [3, 0]), ([3, 12, 1, 80], [2, 0]),
               ([4, 16, 1, 100], [2, 1]), ([4, 16, 1, 100], [0, 1]))
    assert r["removable_share"] == pytest.approx(0.5)
    assert r["decisions"] == 4


def test_bad_counts():
    r = _judge(([1, 1, 1, 1], [0.5, 1]), ([1, 1, 1, 1], [-1, 3]),
               ([1, 1, 1, 1], [np.nan, 1]), ([1, 1, 1, 1], [0, 1]))
    assert r["bad_counts"] == 3 and r["decisions"] == 4
