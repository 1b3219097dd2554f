"""``tag_reduce``: seconds of the operations whose framework name holds
a component, by hand on a small set of operations, and None where there
is nothing to read."""
import pytest

from benchmark.harness import runner, scope_reduce, tag_reduce

INVERSE = "jit(round)/fed.local_steps/lm.delta_rule/delta.inverse/"
BACKWARD = ("jit(round)/fed.local_steps/transpose(jvp(lm.delta_rule))/"
            "delta.inverse/dot_general")
SCAN = "jit(round)/fed.local_steps/lm.delta_rule/while/body/dot_general"


def op(start, dur, framework, lane="/device:TPU:0"):
    return {"lane": lane, "name": f"op@{start}", "start": start,
            "dur": dur, "framework": framework}


def test_seconds_of_the_operations_that_hold_the_tag():
    windows = {"/device:TPU:0": [(0.0, 10.0), (20.0, 30.0)]}
    ops = [
        # a shell that holds the tag and two children: its self time
        # is what the children leave, and the untagged child is not
        # the inverse's
        op(1.0, 4.0, INVERSE + "while"),
        op(1.5, 1.0, INVERSE + "mul"),
        op(3.0, 0.5, None),
        op(6.0, 2.0, SCAN),
        op(21.0, 0.25, BACKWARD),
        # outside the round module's executions: not counted
        op(12.0, 5.0, INVERSE + "mul"),
    ]
    assert tag_reduce.tag_seconds(ops, windows, "delta.inverse") \
        == pytest.approx(2.5 + 1.0 + 0.25)
    # the scope's own reader still gives every one of them to the
    # model's scope: the tag carries no ``lm.`` prefix
    assert {scope_reduce.scope_of(o["framework"]) for o in ops
            if o["framework"]} == {"lm.delta_rule"}


def test_none_where_no_operation_holds_the_tag():
    windows = {"/device:TPU:0": [(0.0, 10.0)]}
    assert tag_reduce.tag_seconds(
        [op(1.0, 4.0, SCAN), op(6.0, 1.0, None)], windows,
        "delta.inverse") is None


@pytest.mark.parametrize("trace", [None, {"rounds": 10}])
def test_reader_returns_none_without_a_trace_or_a_profile(trace):
    """An untraced run, and a traced one whose profile is gone."""
    ctx = {"trace": trace, "cell": {"name": "no.such.cell"}}
    assert runner.load_by_name(
        "layer_metrics", "round_delta_rule_inverse_device_s").read(ctx) \
        is None
