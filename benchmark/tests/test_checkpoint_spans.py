"""The three readers of the checkpoint's sub-spans on a small spans
list worked out by hand: two checkpoints in the window, the second
with a refused link (so its ``model_best.ckpt`` is written), one
warm-up checkpoint and the drain's outside it; and a run of a program
that records none of the spans (the parent of the PR that brought
them)."""
import pytest

from benchmark.harness import runner

READERS = ("checkpoint_serialize_s_per_call",
           "checkpoint_file_write_s_per_call",
           "checkpoint_payload_writes_per_call")


def ctx_of(spans):
    return {"trace": None, "cell": {
        "name": "c", "traffic_file": {"launcher": {"eval_freq": 10}}},
        "window": {"first": 10, "last": 29},
        "spans": {"origin_unix": 0.0, "spans": spans}}


def checkpoint_spans(r, t, fallback, scale=1.0):
    """One synchronous best save after round ``r``, starting at ``t``:
    snapshot 0.3, serialize 0.8, payload 1.0, two metas 0.001 each, the
    link 0.0001 (refused: a second payload file of 0.9)."""
    s = [("checkpoint.snapshot", t, 0.3, {}),
         ("checkpoint.serialize", t + 0.3, 0.8 * scale, {}),
         ("checkpoint.file_write", t + 1.1, 1.0 * scale,
          {"name": "checkpoint.ckpt", "bytes": 330000046}),
         ("checkpoint.file_write", t + 2.1, 0.001,
          {"name": "checkpoint.json", "bytes": 4000}),
         ("checkpoint.link", t + 2.101, 0.0001,
          {"name": "model_best.ckpt", "fallback": fallback})]
    end = t + 2.1011
    if fallback:
        s.append(("checkpoint.file_write", end, 0.9,
                  {"name": "model_best.ckpt", "bytes": 330000046}))
        end += 0.9
    s.append(("checkpoint.file_write", end, 0.001,
              {"name": "model_best.json", "bytes": 4000}))
    end += 0.001
    s.append(("checkpoint.write", t + 0.3, end - t - 0.3, {"round": r + 1}))
    s.append(("checkpoint", t, end - t, {"round": r}))
    return s


def test_readers_by_hand():
    spans = [("data.build", 1.0, 30.0, {})]
    spans += checkpoint_spans(9, 50.0, False, scale=3.0)    # warm-up
    spans += checkpoint_spans(19, 100.0, False)
    spans += checkpoint_spans(29, 110.0, True)
    spans += checkpoint_spans(30, 120.0, True, scale=5.0)   # the drain's
    ctx = ctx_of(spans)
    read = lambda n: runner.load_by_name("layer_metrics", n).read(ctx)
    assert read("checkpoint_serialize_s_per_call") == pytest.approx(0.8)
    # (1.0 + 0.001 + 0.001) and (1.0 + 0.001 + 0.9 + 0.001)
    assert read("checkpoint_file_write_s_per_call") \
        == pytest.approx((1.002 + 1.902) / 2)
    assert read("checkpoint_payload_writes_per_call") \
        == pytest.approx((1 + 2) / 2)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_none_on_a_program_without_the_spans(name):
    spans = [("data.build", 1.0, 30.0, {})]
    for r, t in ((9, 50.0), (19, 100.0), (29, 110.0)):
        spans += [("checkpoint.snapshot", t, 0.3, {}),
                  ("checkpoint.write", t + 0.3, 2.5, {"round": r + 1}),
                  ("checkpoint", t, 2.8, {"round": r})]
    assert runner.load_by_name("layer_metrics", name).read(
        ctx_of(spans)) is None
    # spans of the name, but no checkpoint in the window
    assert runner.load_by_name("layer_metrics", name).read(
        ctx_of(checkpoint_spans(9, 50.0, False))) is None
