"""The trace reduction on a small trace recorded on a TPU v5e: two calls of
the m500-bppod-sweep grid cut to T=50 slots (one ``route_commit`` launch
per slot for the whole grid), inside ``bench.window``."""
import gzip
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "small_sweep.xplane.pb.gz")


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    with gzip.open(TRACE) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return trace_reduce.reduce_profile(pd, {0})


def test_union_merges_overlaps_and_keeps_gaps():
    got = trace_reduce._union(np.array([5.0, 0.0, 1.0, 8.0]),
                              np.array([6.0, 2.0, 3.0, 9.0]))
    np.testing.assert_array_equal(got, [[0, 3], [5, 6], [8, 9]])


def test_op_name_is_the_hlo_instruction():
    assert trace_reduce.op_name(
        "%route_commit.12 = (s32[2]) custom-call(...)") == "route_commit.12"
    assert trace_reduce.op_name("jit__run(123)") == "jit__run(123)"


def test_pinned_numbers_of_the_recorded_trace(red):
    assert red["truncated"] is False and red["dropped_events"] == 0
    assert red["n_device_events"] == PINNED["n_device_events"]
    assert red["window_s"] == pytest.approx(PINNED["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(PINNED["busy_s"], rel=1e-9)
    s, n = trace_reduce.kernel(red, "route_commit")
    assert n == PINNED["route_commit_launches"]
    assert s == pytest.approx(PINNED["route_commit_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert [g[0] for g in red["idle_gaps"]][:1] == [PINNED["longest_gap"]]
    assert len(red["device_ops"]) == 10


# reduced once from the committed trace (TPU v5 lite, jax 0.9.0)
PINNED = dict(n_device_events=33432, window_s=0.095338922,
              busy_s=0.028710917, route_commit_launches=100,
              route_commit_s=0.004623421, longest_gap="bench.sweep.dispatch")
