"""The reduction from a profiler trace to per-layer numbers.

The interval arithmetic is checked on hand-made intervals; the reading of
planes, lines, names and the window on ``data/small.xplane.pb``, a trace
recorded on one v5e by ``record_trace.py``: four jitted 2048 x 2048 bf16
matmuls with 5 ms host sleeps between them, then one grouped-matmul
kernel call, inside a ``bench.window`` annotation."""

from __future__ import annotations

import pytest

import trace_reduce as tr

DATA = tr.__file__.rsplit("/", 1)[0] + "/tests/data/small.xplane.pb"


def test_union_length_merges_overlaps():
    assert tr.union_length([]) == 0
    assert tr.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.union_length([(5, 6), (0, 10)]) == 10


def test_gaps_are_the_holes_within_the_window():
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps([(0, 3), (2, 6)], 0, 6) == []
    assert tr.gaps([], 2, 3) == [(2, 3)]


def test_stable_names_drop_the_numbering():
    assert tr.stable_name("fusion.12") == "fusion"
    assert tr.stable_name("copy.3.1") == "copy"
    assert tr.stable_name("convolution") == "convolution"


@pytest.fixture(scope="module")
def small():
    return tr.reduce_file(DATA)


def test_self_times_take_nested_ops_out_of_their_parent():
    got = tr.self_times([(0, 10, "while"), (1, 3, "a"), (4, 6, "b"),
                         (12, 13, "c")])
    assert sorted(got) == [("a", 2), ("b", 2), ("c", 1), ("while", 6)]


def test_recorded_trace_window_and_programs(small):
    assert small.n_devices == 1
    # the bench.window annotation, on the host clock
    assert small.window_s == pytest.approx(0.026837319)
    # the first matmul ran 1.1 ms before the device clock's window start
    # (see the module doc); three of four fall in the window, and the
    # kernel call
    assert {k: len(v) for k, v in small.modules.items()} == {
        "jit__lambda": 3, "jit_gmm": 1}
    assert small.module_times("^jit_gmm$") == [pytest.approx(13.088e-6)]


def test_recorded_trace_ops_and_busy_time(small):
    secs, n = small.op_time("^grouped_matmul$")
    assert n == 1 and secs == pytest.approx(13.085e-6)
    fusion_s, fusion_n = small.op_time("^fusion$")
    assert fusion_n == 3 and fusion_s == pytest.approx(272.793e-6)
    # busy is the union of every op in the window; the rest is idle
    assert small.busy_s == pytest.approx(285.924e-6)
    assert small.top_ops(2)[0][0] == "fusion"


def test_recorded_trace_idle_gaps_are_named(small):
    names = [name for name, _ in small.idle_gaps]
    secs = [s for _, s in small.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    # the 5 ms host sleeps between matmuls lie under no benchmark span
    assert names[0] == "no benchmark span"
    assert sum(secs) <= small.window_s - small.busy_s + 1e-9
