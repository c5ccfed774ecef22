"""The trace reduction, on hand-made intervals and on a recorded trace.

``data/multiset_3calls.xplane.pb`` was recorded on one TPU v5 lite: three
``evaluate_multiset`` calls (n=4,096, l=256, k=10, d=100, Pallas fp32),
each in an ``evaluate`` span, 5 ms apart, inside one ``window`` span."""
import os

import pytest

from bench import trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "multiset_3calls.xplane.pb")


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [
        (0, 4), (5, 7), (9, 10)]


def test_gaps_are_the_complement_within_the_window():
    busy = [(2, 3), (5, 8)]
    assert trace.gaps(busy, 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert trace.gaps(busy, 2, 8) == [(3, 5)]
    assert trace.gaps([], 1, 4) == [(1, 4)]


def test_clip_keeps_only_the_part_inside():
    assert trace.clip([(0, 5), (6, 7), (9, 12)], 4, 10) == [
        (4, 5), (6, 7), (9, 10)]


def test_control_flow_is_not_a_top_op():
    assert trace.CONTROL_FLOW.search(
        "(f32[8]{0}, s32[]) while((f32[8]{0}, s32[]) %t), condition=%c")
    assert not trace.CONTROL_FLOW.search("f32[8]{0} fusion(f32[8] %a)")


def test_labels():
    assert trace.op_label("%fusion.3 = f32[8]{0} fusion(f32[8] %a)") == \
        "fusion.3"
    assert trace.module_label("jit__exemplar_eval_padded(1107)") == \
        "_exemplar_eval_padded"


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(RECORDED, span_names=("evaluate",))


def test_recorded_window_and_busy(recorded):
    # the window span lasted 40,591,904 ns (hand-read from the trace)
    assert recorded.window_s == pytest.approx(0.040591904, rel=1e-9)
    assert recorded.devices == 1
    assert 0 < recorded.busy_s < recorded.window_s
    assert recorded.spans == {"evaluate": (3, pytest.approx(0.025303335))}


def test_recorded_kernel_is_the_top_op(recorded):
    # three custom calls of 112,293 + 112,293 + 112,295 ns
    name, seconds = recorded.top_ops[0]
    assert name == "_exemplar_eval_padded:_exemplar_eval_padded.1"
    assert seconds == pytest.approx(336_881e-9, rel=1e-9)
    assert sum(s for _, s in recorded.top_ops) <= recorded.busy_s + 1e-12


def test_recorded_gaps_between_calls_have_no_span(recorded):
    # the three longest gaps hold the 5 ms sleeps between the calls,
    # outside every evaluate span
    assert [name for name, _ in recorded.idle_gaps[:3]] == ["no span"] * 3
    assert all(s > 5e-3 for _, s in recorded.idle_gaps[:3])
    assert {name for name, _ in recorded.idle_gaps} <= {"evaluate",
                                                        "no span"}
