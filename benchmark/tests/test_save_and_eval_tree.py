"""The twelve readers of ISSUE 37 on a small spans list worked out by
hand: a warm-up cycle, two cycles in the window (rounds 10 to 29) and
the drain's save outside it; a program without the new spans (the
parent of the PR that brought them) reads None or, where the span was
there before, its number; and the ResNet cell's four older checkpoint
readers read the same with the new spans present as without."""
import pytest

from benchmark.harness import runner
from benchmark.tests.test_checkpoint_spans import checkpoint_spans, ctx_of

NEW = ("checkpoint_snapshot_s_per_call", "checkpoint_layout_s_per_call",
       "checkpoint_digest_s_per_call", "checkpoint_data_write_s_per_call",
       "checkpoint_fsync_s_per_call", "checkpoint_replace_s_per_call",
       "checkpoint_payload_gib_per_call",
       "checkpoint_unspanned_s_per_call",
       "checkpoint_rss_growth_gib_per_call", "eval_input_s_per_call",
       "eval_run_s_per_call", "eval_rss_growth_gib_per_call")
OLD = ("checkpoint_serialize_s_per_call",
       "checkpoint_file_write_s_per_call",
       "checkpoint_payload_writes_per_call",
       "checkpoint_borrowed_bytes_share")
GIB = 2 ** 30


def read(name, spans):
    return runner.load_by_name("layer_metrics", name).read(ctx_of(spans))


def save_tree(t, grown, retried=False):
    """The new spans of the save that ``checkpoint_spans(r, t, False)``
    lays out (snapshot 0.3 from t, serialize 0.8, payload file 1.0, two
    metas of 0.001, the link of 0.0001): layout 0.5 + digest 0.29
    inside serialize; the payload's data 0.6 (after a failed attempt of
    0.05 where ``retried``), fsync 0.3, rename 0.01; each meta 0.0002 +
    0.0006 + 0.0001; and the resident set on the loop's span."""
    s = [("checkpoint.layout", t + 0.3, 0.5, {"relaid_s": 0.45}),
         ("checkpoint.digest", t + 0.805, 0.29, {"bytes": GIB // 2})]
    at = t + 1.1
    if retried:
        s.append(("checkpoint.file_write.data", at, 0.05, {}))
        at += 0.06
    s += [("checkpoint.file_write.data", at, 0.6, {}),
          ("checkpoint.file_write.fsync", at + 0.6, 0.3, {}),
          ("checkpoint.file_write.rename", at + 0.9, 0.01, {})]
    for meta in (t + 2.1, t + 2.1011):
        s += [("checkpoint.file_write.data", meta, 0.0002, {}),
              ("checkpoint.file_write.fsync", meta + 0.0002, 0.0006, {}),
              ("checkpoint.file_write.rename", meta + 0.0008, 0.0001, {})]
    return s, {"vm_rss_enter": 5 * GIB, "vm_rss_exit": 5 * GIB + grown}


def with_loop_args(spans, args):
    return [(n, t, d, dict(a, **args)) if n == "checkpoint"
            else (n, t, d, a) for n, t, d, a in spans]


def eval_spans(r, t, grown, new=True):
    """The loop's ``eval`` of 0.08 s and, where ``new``, its four
    children and its resident set (grown by ``grown`` bytes)."""
    if not new:
        return [("eval", t, 0.08, {"round": r})]
    return [("eval.batches", t, 0.04, {"bytes": 126, "rows": 10000,
                                       "pad_rows": 240}),
            ("eval.h2d", t + 0.04, 0.02, {"bytes": 126}),
            ("eval.dispatch", t + 0.06, 0.001, {}),
            ("eval.fetch", t + 0.061, 0.015, {}),
            ("eval", t, 0.08, {"round": r, "vm_rss_enter": 4 * GIB,
                               "vm_rss_exit": 4 * GIB + grown})]


def round_spans(r, t, wait):
    return [("round.wait", t, wait, {"round": r}),
            ("scalar_fetch", t + wait, 0.001, {"round": r}),
            ("round", t - 0.004, wait + 0.004, {"round": r})]


def run_spans(new=True):
    """Rounds 0 to 30: evaluation and save after 9, 19, 29, the drain
    after 30."""
    spans = [("data.build", 1.0, 30.0, {})]
    for r in range(31):
        spans += round_spans(r, 40.0 + 3 * r, 0.3 if r < 2 else 0.1)
    for r, grown, retried in ((9, GIB // 2, False), (19, GIB // 4, False),
                              (29, 0, True)):
        t = 41.0 + 3 * r
        spans += eval_spans(r, t - 0.5, grown // 4, new)
        save = checkpoint_spans(r, t, False)
        if new:
            tree, loop_args = save_tree(t, grown, retried)
            save = with_loop_args(save, loop_args) + tree
        spans += save
    drain = checkpoint_spans(30, 135.0, False, scale=5.0)
    if new:
        tree, loop_args = save_tree(135.0, GIB, False)
        drain = with_loop_args(drain, loop_args) + tree
    return spans + [(n, t, d, dict(a, drain=True) if n == "checkpoint"
                     else a) for n, t, d, a in drain]


def test_new_readers_by_hand():
    spans = run_spans()
    assert read("checkpoint_snapshot_s_per_call", spans) \
        == pytest.approx(0.3)
    assert read("checkpoint_layout_s_per_call", spans) \
        == pytest.approx(0.5)
    assert read("checkpoint_digest_s_per_call", spans) \
        == pytest.approx(0.29)
    # the payload's and the two metas'; the second save's failed attempt
    assert read("checkpoint_data_write_s_per_call", spans) \
        == pytest.approx((0.6004 + 0.6504) / 2)
    assert read("checkpoint_fsync_s_per_call", spans) \
        == pytest.approx(0.3012)
    # three renames and the link
    assert read("checkpoint_replace_s_per_call", spans) \
        == pytest.approx(0.0102 + 0.0001)
    assert read("checkpoint_payload_gib_per_call", spans) \
        == pytest.approx(0.5)
    assert read("checkpoint_rss_growth_gib_per_call", spans) \
        == pytest.approx(0.125)
    # 2.1021 of span less snapshot 0.3, layout 0.5, digest 0.29, the
    # payload's 0.91 (0.96 with the failed attempt), the metas' 0.0018,
    # the link 0.0001
    assert read("checkpoint_unspanned_s_per_call", spans) \
        == pytest.approx((0.1002 + 0.0502) / 2)
    assert read("eval_input_s_per_call", spans) == pytest.approx(0.06)
    assert read("eval_run_s_per_call", spans) == pytest.approx(0.016)
    assert read("eval_rss_growth_gib_per_call", spans) \
        == pytest.approx(0.03125)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_on_a_program_without_the_new_spans(name):
    got = read(name, run_spans(new=False))
    if name == "checkpoint_snapshot_s_per_call":
        assert got == pytest.approx(0.3)    # the span was there before
    else:
        # checkpoint.link was there too: alone it is no reading
        assert got is None
    # no span at all, and no window call
    assert read(name, [("data.build", 1.0, 30.0, {})]) is None
    warm_up = [s for s in run_spans() if s[1] < 70.0]
    assert read(name, warm_up) is None


def test_a_save_that_is_not_the_best_has_no_link():
    spans = [s for s in run_spans() if s[0] != "checkpoint.link"]
    assert read("checkpoint_replace_s_per_call", spans) \
        == pytest.approx(0.0102)


@pytest.mark.parametrize("name", OLD)
def test_old_checkpoint_readers_read_the_same_with_the_new_spans(name):
    counts = {"pieces": 400, "borrowed_bytes": 3, "relaid_bytes": 297,
              "copied_bytes": 0}
    with_counts = lambda spans: [
        (n, t, d, dict(a, **counts)) if n == "checkpoint.serialize"
        else (n, t, d, a) for n, t, d, a in spans]
    old, new = (read(name, with_counts(run_spans(new=flag)))
                for flag in (False, True))
    assert old is not None and new == old
