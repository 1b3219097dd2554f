"""The stage reduction on a small recorded event list, against answers
worked out by hand (see data/small_stages.json), and every new reader
on a run that has nothing for it to read."""
import json
import os

import pytest

from benchmark.harness import runner, stage_reduce as sr

HERE = os.path.dirname(os.path.abspath(__file__))
NEW_READERS = (
    "round_local_steps_device_s", "round_gather_device_s",
    "round_commit_device_s", "round_unstaged_device_s",
    "round_conv_device_s", "round_dispatch_s", "scalar_fetch_s_per_round",
    "round_record_s", "scalar_fetch_programs_per_round",
    "backend_compiles_in_window", "first_round_compile_s",
    "state_build_s", "data_load_s", "round_forward_backward_device_s",
    "round_augment_device_s", "round_opt_step_device_s",
    "eval_forward_device_s", "data_layout_s")


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(HERE, "data", "small_stages.json")) as f:
        d = json.load(f)
    d["annotations"] = [tuple(a) for a in d["annotations"]]
    return d


@pytest.fixture(scope="module")
def red(doc):
    return sr.reduce_stages(doc["ops"], doc["modules"], doc["annotations"],
                            doc["rounds"])


def test_stage_is_the_first_fed_or_eval_component():
    assert sr.stage_of(
        "jit(round_fn)/jit(main)/vmap(fed.local_steps)/while/body/"
        "fed.forward_backward/transpose(jvp(conv))") == "fed.local_steps"
    assert sr.stage_of("jit(run)/eval.forward/while/body/dot") \
        == "eval.forward"
    assert sr.stage_of("jit(round_fn)/jit(main)/while/body/add") is None
    assert sr.stage_of(None) is None


def test_stage_and_category_come_from_the_metadata_record():
    # as the v5e's trace has them: the framework name and the category
    # are stats of the event's metadata record
    meta = {"tf_op": "jit(round_fn)/vmap(fed.local_steps)/while/body/"
                     "closed_call/fed.forward_backward/jvp(ResNet)/conv:",
            "hlo_category": "convolution fusion"}
    assert sr.op_stage_and_conv(meta) == (
        "fed.local_steps", "fed.forward_backward", True)
    # the compiler's copy of an argument carries the argument's name
    assert sr.op_stage_and_conv(
        {"tf_op": "data.x:", "hlo_category": "data formatting"}) == (
        None, None, False)
    # no record: no stage, and no guess at the category
    assert sr.op_stage_and_conv({}) == (None, None, None)


def test_round_module_is_the_one_run_once_a_round(red):
    # jit_convert_element_type also runs twice, for 0.2 s against 8 s
    assert red["round_module"] == "jit_round_fn"


def test_stage_seconds_take_the_body_off_the_while_shell(red):
    # a round: copy 0.5 + shell 2.5 - body 2.0 + body 2.0 = 3.0 s under
    # fed.local_steps; the shell itself keeps 0.5 s
    assert red["stage_s"]["fed.local_steps"] == pytest.approx(2 * 3.0)
    assert red["stage_s"]["fed.gather"] == pytest.approx(2 * 0.5)
    assert red["stage_s"]["fed.aggregate"] == pytest.approx(2 * 0.3)
    # the evaluation program's operations are outside the round module
    assert "eval.forward" not in red["stage_s"]


def test_local_steps_by_innermost_scope(red):
    # the body's two fusions carry an inner scope; the hoisted copy and
    # the shell's own 0.5 s stay with fed.local_steps itself
    assert red["local_s"] == {
        "fed.forward_backward": pytest.approx(2 * 1.0),
        "fed.opt_step": pytest.approx(2 * 1.0),
        "fed.local_steps": pytest.approx(2 * 1.0)}
    assert sum(red["local_s"].values()) \
        == pytest.approx(red["stage_s"]["fed.local_steps"])


def test_evaluation_seconds_inside_its_own_program(red):
    # jit_run holds the one eval.* operation: 0.8 s under the scope of
    # its 1 s, one execution; copy.11 there has no stage
    assert (red["eval_s"], red["eval_runs"]) == (pytest.approx(0.8), 1.0)


def test_unstaged_remainder_and_its_operations(red):
    assert red["unstaged_s"] == pytest.approx(2 * 0.2)
    assert red["unstaged_ops"] == {"copy": pytest.approx(0.4)}
    # stages + unstaged = every self second inside the round module,
    # which here is also its wall: 2 x 4 s
    assert sum(red["stage_s"].values()) + red["unstaged_s"] \
        == pytest.approx(red["self_s"]) == pytest.approx(8.0)


def test_convolution_seconds(red):
    assert red["conv_s"] == pytest.approx(2 * 1.0)


def test_gaps_are_named_by_the_innermost_annotation_over_them(red):
    # the device idles over (14.04,15) (15.1,15.2) (15.3,20) (24,24.5)
    # (24.6,30) (31,33); each part takes the shortest annotation there
    got = dict(red["gap_labels"])
    assert got["scalar_fetch"] == pytest.approx(
        0.95 + 0.1 + 0.2 + 0.4 + 0.4)
    assert got["none"] == pytest.approx(3.8 + 4.0)
    assert got["checkpoint"] == pytest.approx(1.8)
    assert got["round.record"] == pytest.approx(1.0)
    assert got["eval"] == pytest.approx(0.5 + 0.2)
    assert got["round.dispatch"] == pytest.approx(0.2)
    assert got["round.wait"] == pytest.approx(0.01 + 0.1)
    assert "round" not in got        # always under dispatch or wait
    assert red["gap_labels"][0][0] == "none"    # longest first


def test_programs_that_start_under_the_wait_and_the_fetch(red):
    # jit__mean starts under the end of round.wait (the device's clock
    # is ahead); the round program itself, under round.wait too, is
    # not the fetch's
    assert red["fetch_programs"] == {
        "jit__mean": {"runs": 1, "seconds": pytest.approx(0.04)},
        "jit_convert_element_type": {"runs": 2,
                                     "seconds": pytest.approx(0.2)},
        "jit_add": {"runs": 1, "seconds": pytest.approx(0.1)}}


def test_device_readers_on_the_reduction(red):
    ctx = {"trace": {"rounds": 2}, "stages": red}
    read = lambda n: runner.load_by_name("layer_metrics", n).read(ctx)
    assert read("round_local_steps_device_s") == pytest.approx(3.0)
    assert read("round_gather_device_s") == pytest.approx(0.5)
    assert read("round_commit_device_s") == pytest.approx(0.3)
    assert read("round_unstaged_device_s") == pytest.approx(0.2)
    assert read("round_conv_device_s") == pytest.approx(1.0)
    assert read("scalar_fetch_programs_per_round") == pytest.approx(2.0)
    assert read("round_forward_backward_device_s") == pytest.approx(1.0)
    assert read("round_opt_step_device_s") == pytest.approx(1.0)
    assert read("round_augment_device_s") == 0.0    # no such operation
    assert read("eval_forward_device_s") == pytest.approx(0.8)


def test_a_program_without_scopes_or_annotations_reads_none(doc):
    ops = [dict(o, stage=None, inner=None) for o in doc["ops"]]
    red = sr.reduce_stages(ops, doc["modules"], [], doc["rounds"])
    ctx = {"trace": {"rounds": 2}, "stages": red}
    read = lambda n: runner.load_by_name("layer_metrics", n).read(ctx)
    for name in ("round_local_steps_device_s", "round_gather_device_s",
                 "round_commit_device_s", "round_unstaged_device_s",
                 "round_forward_backward_device_s",
                 "round_augment_device_s", "round_opt_step_device_s",
                 "eval_forward_device_s",
                 "scalar_fetch_programs_per_round"):
        assert read(name) is None, name
    # what needs no scope is still read
    assert read("round_conv_device_s") == pytest.approx(1.0)
    # and a trace without categories says nothing of convolutions
    red = sr.reduce_stages([dict(o, conv=None) for o in doc["ops"]],
                           doc["modules"], [], doc["rounds"])
    assert runner.load_by_name("layer_metrics", "round_conv_device_s").read(
        {"trace": {"rounds": 2}, "stages": red}) is None
    assert dict(red["gap_labels"]) == {"none": pytest.approx(
        0.96 + 0.1 + 4.7 + 0.5 + 5.4)}


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_returns_none_where_there_is_nothing_to_read(name):
    """An untraced run of a program that records none of the new spans
    (the parent of the PR that brought them)."""
    ctx = {"trace": None,
           "cell": {"name": "no.such.cell",
                    "traffic_file": {"launcher": {"eval_freq": 10}}},
           "window": {"first": 10, "last": 29},
           "spans": {"origin_unix": None, "spans": [
               ("data.build", 1.0, 30.0, {}),
               ("round", 40.0, 10.0, {"round": 0}),
               ("scalar_fetch_old", 50.0, 0.01, {"round": 0})]}}
    assert runner.load_by_name("layer_metrics", name).read(ctx) is None


def span_ctx():
    spans = [("data.build", 1.0, 30.0, {}), ("data.load", 1.0, 20.0, {}),
             ("data.partition", 21.0, 0.5, {}),
             ("data.layout", 21.5, 8.0, {}),
             ("trainer.build", 31.0, 12.0, {}),
             ("data.h2d", 33.0, 9.0, {}),      # inside trainer.build
             ("state.init", 43.0, 5.0, {}),
             ("round", 50.0, 10.0, {"round": 0}),
             ("jax.trace", 50.5, 2.0, {"fun": "round_fn"}),
             ("jax.lower", 52.5, 1.0, {"fun": "jit_round_fn"}),
             ("jax.compile", 53.5, 5.0, {"fun": "jit_round_fn"}),
             ("jax.cache_load", 54.0, 4.0, {}),     # inside the compile
             ("jax.compile", 59.5, 1.0, {"fun": "late"})]  # half inside
    t = 100.0
    for r in range(10, 30):
        spans.append(("round", t, 0.13, {"round": r}))
        spans.append(("round.dispatch", t, 0.001 * (r % 3 + 1),
                      {"round": r}))
        spans.append(("scalar_fetch", t + 0.13, 0.01, {"round": r}))
        spans.append(("round.record", t + 0.14, 0.002, {"round": r}))
        spans.append(("round.record", t + 0.15, 0.001, {"round": r}))
        t += 0.2
    # one eager compile between rounds 14 and 15, one load outside any
    spans.append(("jax.compile", 100.95, 0.02, {"fun": "convert"}))
    spans.append(("jax.cache_load", 101.5, 0.01, {}))
    # and one after the window's last round: not counted
    spans.append(("jax.compile", t + 5.0, 0.02, {"fun": "after"}))
    return {"trace": None, "cell": {
        "name": "c", "traffic_file": {"launcher": {"eval_freq": 10}}},
        "window": {"first": 10, "last": 29},
        "spans": {"origin_unix": 0.0, "spans": spans}}


def test_span_readers_by_hand():
    ctx = span_ctx()
    read = lambda n: runner.load_by_name("layer_metrics", n).read(ctx)
    # train-only rounds 10..18, 20..28: r % 3 + 1 ms, six of each
    assert read("round_dispatch_s") == pytest.approx(0.002)
    assert read("scalar_fetch_s_per_round") == pytest.approx(0.01)
    assert read("round_record_s") == pytest.approx(0.003)  # two a round
    # trace 2 + lower 1 + compile 5 (the load inside it once) + the
    # half of the late compile that round 0's span holds
    assert read("first_round_compile_s") == pytest.approx(8.5)
    # trainer.build 12 (data.h2d inside it once) + state.init 5
    assert read("state_build_s") == pytest.approx(17.0)
    assert read("data_load_s") == pytest.approx(20.0)
    assert read("data_layout_s") == pytest.approx(8.5)
    assert read("backend_compiles_in_window") == 2.0
