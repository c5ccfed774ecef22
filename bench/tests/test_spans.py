"""The split of the device's idle time by host span (``bench/spans.py``),
on hand-made intervals and on recorded traces.

``data/multiset_spans_3calls.xplane.pb`` was recorded on one TPU v5 lite
as ``data/multiset_3calls.xplane.pb`` was (three ``evaluate_multiset``
calls at n=4,096, l=256, k=10, d=100, Pallas fp32, each in an
``evaluate`` span, 5 ms apart, inside one ``window`` span), from a program
that opens its own spans (``src/repro/core/tracing.py``) and names its
kernels."""
import os

import pytest

from bench import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "multiset_3calls.xplane.pb")
NEW = os.path.join(DATA, "multiset_spans_3calls.xplane.pb")
#: the program's spans, innermost first, then the harness's
PROGRAM = ("evaluate_multiset.e0_distances", "exemplar_eval",
           "evaluate_multiset")
NESTED = PROGRAM + ("evaluate",)


def test_split_gives_each_part_to_the_innermost_span():
    idle = [(0, 10), (20, 30), (40, 50)]
    covered = [("child", [(2, 4), (22, 23)]),
               ("parent", [(1, 6), (21, 29)]),
               ("outer", [(0, 35)])]
    split = spans.split_idle(idle, covered)
    assert split == {"child": 3, "parent": 3 + 7, "outer": 5 + 2,
                     "no span": 10}
    assert sum(split.values()) == 30


def test_split_of_overlapping_spans_counts_each_instant_once():
    split = spans.split_idle([(0, 10)], [("a", [(0, 6)]), ("b", [(4, 8)])])
    assert split == {"a": 6, "b": 2, "no span": 2}


def test_split_with_no_spans_is_all_no_span():
    assert spans.split_idle([(0, 2), (5, 6)], []) == {"no span": 3}


@pytest.mark.parametrize("path,names", [(OLD, ("evaluate",)),
                                        (NEW, NESTED)])
def test_split_adds_up_to_the_window_idle_time(path, names):
    reduced = trace.reduce(path, span_names=names)
    split = spans.idle_by_span(path, names)
    assert set(split) == set(names) | {"no span"}
    assert all(v >= 0 for v in split.values())
    assert sum(split.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s, rel=1e-9)
    # the 5 ms sleeps between the three calls lie outside every span
    assert split["no span"] > 3 * 5e-3


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(NEW, span_names=("evaluate",) + PROGRAM)


def test_recorded_program_spans_open_once_per_call(recorded):
    assert {name: count for name, (count, _) in recorded.spans.items()} == \
        {"evaluate": 3, "evaluate_multiset": 3,
         "evaluate_multiset.e0_distances": 3, "exemplar_eval": 3}
    secs = {name: s for name, (_, s) in recorded.spans.items()}
    # children inside their parent, the parent inside the harness's span
    assert secs["evaluate_multiset.e0_distances"] + secs["exemplar_eval"] \
        <= secs["evaluate_multiset"] <= secs["evaluate"]


def test_recorded_kernel_carries_its_own_name(recorded):
    name, _ = recorded.top_ops[0]
    assert name == "_exemplar_eval_padded:exemplar_eval_flat.1"


def test_recorded_idle_gaps_reach_program_spans(recorded):
    assert {name for name, _ in recorded.idle_gaps} & set(PROGRAM)
    split = spans.idle_by_span(NEW, NESTED)
    assert sum(split[name] for name in PROGRAM) > 0
