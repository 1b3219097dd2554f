"""The reader of the serializer's byte counts on spans worked out by
hand: two checkpoints in the window (one all borrowed, one with leaves
laid out anew and a leaf the fallback packed), a warm-up checkpoint and
the drain's outside it; and a run of a program whose
``checkpoint.serialize`` span holds no counts (the parent of the PR
that brought them)."""
import pytest

from benchmark.harness import runner
from benchmark.tests.test_checkpoint_spans import checkpoint_spans, ctx_of


def read(spans):
    return runner.load_by_name(
        "layer_metrics", "checkpoint_borrowed_bytes_share").read(
            ctx_of(spans))


def with_counts(spans, borrowed, copied, relaid=0):
    return [(n, t, d, dict(a, pieces=400, borrowed_bytes=borrowed,
                           relaid_bytes=relaid, copied_bytes=copied))
            if n == "checkpoint.serialize" else (n, t, d, a)
            for n, t, d, a in spans]


def test_share_by_hand():
    spans = with_counts(checkpoint_spans(9, 50.0, False), 0, 500)  # warm-up
    spans += with_counts(checkpoint_spans(19, 100.0, False), 3000, 0)
    spans += with_counts(checkpoint_spans(29, 110.0, True), 2000, 1000, 500)
    spans += with_counts(checkpoint_spans(30, 120.0, True), 0, 700)  # drain
    assert read(spans) == pytest.approx(5000 / 6500)


def test_leaves_laid_out_anew_are_not_borrowed():
    # the chip's case: the client state arrives client-minor
    assert read(with_counts(checkpoint_spans(19, 100.0, False),
                            3262820, 0, relaid=326976480)) \
        == pytest.approx(3262820 / 330239300)


def test_all_borrowed_reads_one():
    assert read(with_counts(checkpoint_spans(19, 100.0, False),
                            330000000, 0)) == 1.0


def test_none_on_a_program_without_the_counts():
    # the span, but no counts in it: the payload was one object
    assert read(checkpoint_spans(19, 100.0, False)
                + checkpoint_spans(29, 110.0, True)) is None
    # no span of the name at all
    assert read([("checkpoint", 100.0, 2.8, {"round": 19})]) is None
    # counts, but no checkpoint in the window
    assert read(with_counts(checkpoint_spans(9, 50.0, False), 1, 0)) is None
