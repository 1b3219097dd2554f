"""The copied trace reduction on a small recorded trace, against
answers worked out by hand (see data/small_trace.json)."""
import json
import os

import pytest

from benchmark.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(doc):
    return tr.reduce_events(doc["events"], tuple(doc["window"]))


def test_busy_is_the_interval_union_averaged_over_lanes(reduced):
    # lane 0: [1,5] + [6,8] = 6 s (the all-gather and the copy overlap,
    # the while shell covers its body); lane 1: [2,4] + [8,9] = 3 s
    assert reduced["lanes"] == 2
    assert reduced["busy_s"] == pytest.approx((6.0 + 3.0) / 2)
    assert reduced["window_s"] == pytest.approx(10.0)


def test_self_time_takes_nested_children_off_the_shell(reduced):
    # while 4 s - convolution 2 s - fusion 1.5 s = 0.5 s of its own
    ops = reduced["op_s"]
    assert ops["while"] == pytest.approx(0.5 / 2)
    assert ops["convolution"] == pytest.approx((2.0 + 2.0) / 2)
    assert ops["fusion"] == pytest.approx(1.5 / 2)


def test_category_sums(reduced):
    cat = reduced["category_s"]
    assert cat["custom_call"] == pytest.approx(0.5 / 2)
    # all-gather 1 s on lane 0 (the copy that starts inside it ends
    # after it, so it is no child) + all-reduce 1 s on lane 1
    assert cat["collective"] == pytest.approx((1.0 + 1.0) / 2)
    assert cat["matmul_conv_mxu"] == pytest.approx(4.0 / 2)
    assert cat["control_flow"] == pytest.approx(0.5 / 2)
    assert cat["elementwise"] == pytest.approx(1.5 / 2)
    assert cat["copy_reshape_transpose"] == pytest.approx(1.0 / 2)
    assert "other" not in cat


def test_idle_share_of_the_window(reduced):
    assert 1.0 - reduced["busy_s"] / reduced["window_s"] \
        == pytest.approx(0.55)


def test_gaps_of_the_first_lane_labelled_by_host_span(doc, reduced):
    # lane 0 is idle over [0,1], [5,6], [8,10]
    assert sorted(reduced["gaps"]) == [(0.0, 1.0), (5.0, 6.0), (8.0, 10.0)]
    labelled = tr.label_gaps(reduced["gaps"],
                             [tuple(s) for s in doc["host_spans"]])
    # [8,10] is under the checkpoint (1.9 s of it); [5,6] has 0.7 s of
    # eval against 0.2 s of round; [0,1] has 0.5 s of round
    assert labelled[0] == ["checkpoint", pytest.approx(2.0)]
    assert dict(map(tuple, labelled)) == {
        "checkpoint": pytest.approx(2.0), "eval": pytest.approx(1.0),
        "round": pytest.approx(1.0)}


def test_window_clips_events(doc):
    r = tr.reduce_events(doc["events"], (2.0, 7.0))
    # lane 0: [2,5] + [6,7] = 4 s; lane 1: [2,4] = 2 s
    assert r["busy_s"] == pytest.approx((4.0 + 2.0) / 2)


@pytest.mark.parametrize("name,cat", [
    ("tpu_custom_call.1", "custom_call"), ("all-reduce.3", "collective"),
    ("convolution.9", "matmul_conv_mxu"), ("convert.2", "elementwise"),
    ("reduce-window.1", "reduce"), ("dynamic-slice.4",
                                    "copy_reshape_transpose"),
    ("while.1", "control_flow"), ("mystery", "other")])
def test_taxonomy(name, cat):
    assert tr.categorize(name) == cat


def test_busy_inside_a_programs_executions(doc):
    # two executions of one program on lane 0, [0.5,5.5] and [5.8,8.2],
    # and one of another on lane 1, [1.5,4.5]
    modules = [
        {"lane": "/device:TPU:0", "name": "jit_round(17)", "start": 0.5,
         "dur": 5.0},
        {"lane": "/device:TPU:0", "name": "jit_round(17)", "start": 5.8,
         "dur": 2.4},
        {"lane": "/device:TPU:1", "name": "jit_eval(3)", "start": 1.5,
         "dur": 3.0}]
    m = tr.module_busy(doc["events"], modules)
    # lane 0's union is [1,5] + [6,8]: 4 s in the first run, 2 s in the
    # second; averaged over the two lanes that carry a module line
    assert m["jit_round"]["runs"] == pytest.approx(2 / 2)
    assert m["jit_round"]["busy_s"] == pytest.approx((4.0 + 2.0) / 2)
    assert m["jit_round"]["module_s"] == pytest.approx(7.4 / 2)
    assert m["jit_eval"]["busy_s"] == pytest.approx(2.0 / 2)
