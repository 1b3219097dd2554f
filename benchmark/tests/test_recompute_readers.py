"""The two readers of what a rematerialized layer keeps and runs again:
``round_recompute_device_s`` by hand on a small set of operations (JAX's
``rematted_computation`` component of the framework name, whatever
``lm.*`` scope holds the operation), None without a trace or where the
program runs nothing again; ``round_kept_product_share`` from the
round's rows, a share between 0 and 1, None on a parent's rows."""
import pytest

from benchmark.harness import runner, scope_reduce, tag_reduce

TAG = "rematted_computation"
BACK = "jit(round)/fed.local_steps/fed.forward_backward/transpose(jvp("
MLP_AGAIN = BACK + "lm.mlp))/checkpoint/rematted_computation/dot_general"
NORM_AGAIN = BACK + "checkpoint))/rematted_computation/rsqrt"
LOOP_AGAIN = BACK + "lm.loop))/while/body/checkpoint/" \
    "rematted_computation/lm.attention/exp"
MLP_BACKWARD = BACK + "lm.mlp))/checkpoint/dot_general"
MLP_FORWARD = "jit(round)/fed.local_steps/fed.forward_backward/" \
    "jvp(lm.mlp)/checkpoint/dot_general"


def op(start, dur, framework, lane="/device:TPU:0"):
    return {"lane": lane, "name": f"op@{start}", "start": start,
            "dur": dur, "framework": framework}


def read(name, ctx):
    return runner.load_by_name("layer_metrics", name).read(ctx)


def test_seconds_of_what_the_backward_pass_runs_again():
    windows = {"/device:TPU:0": [(0.0, 10.0)]}
    ops = [op(0.5, 1.0, MLP_FORWARD), op(2.0, 0.75, MLP_AGAIN),
           op(3.0, 0.125, NORM_AGAIN), op(4.0, 0.5, LOOP_AGAIN),
           op(5.0, 2.0, MLP_BACKWARD),
           # outside the round module's executions: not counted
           op(12.0, 1.0, MLP_AGAIN)]
    assert tag_reduce.tag_seconds(ops, windows, TAG) \
        == pytest.approx(0.75 + 0.125 + 0.5)
    # the scopes' own readers keep every one of them: the tag is no
    # ``lm.`` component
    assert [scope_reduce.scope_of(o["framework"]) for o in ops[:5]] \
        == ["lm.mlp", "lm.mlp", None, "lm.attention", "lm.mlp"]


def test_none_where_the_program_runs_nothing_again():
    windows = {"/device:TPU:0": [(0.0, 10.0)]}
    assert tag_reduce.tag_seconds(
        [op(0.5, 1.0, MLP_FORWARD), op(5.0, 2.0, MLP_BACKWARD)], windows,
        TAG) is None


@pytest.mark.parametrize("trace", [None, {"rounds": 10}])
def test_recompute_reader_returns_none_without_a_trace_or_a_profile(trace):
    ctx = {"trace": trace, "cell": {"name": "no.such.cell"}}
    assert read("round_recompute_device_s", ctx) is None


@pytest.mark.parametrize("shares,want", [
    ([1.0, 1.0, 1.0], 1.0),
    ([0.0, 0.0], 0.0),                  # remat on, nothing fits
    ([0.4375, 0.4375, 0.4375], 0.4375),
])
def test_kept_share_is_the_rows_counter(shares, want):
    rows = [{"round": i, "round_s": 0.7, "lm_kept_product_share": s,
             "lm_kept_residual_bytes": 1e9 * s}
            for i, s in enumerate(shares)]
    got = read("round_kept_product_share", {"rows": rows})
    assert got == want and 0.0 <= got <= 1.0


def test_kept_share_is_none_on_rows_without_the_counter():
    """The parent's rows, and a model without ``remat``."""
    rows = [{"round": 0, "round_s": 0.7, "tokens_trained": 8192.0}]
    assert read("round_kept_product_share", {"rows": rows}) is None
    assert read("round_kept_product_share", {"rows": []}) is None
