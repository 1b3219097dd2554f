"""Window arithmetic on a synthetic list of callback stamps and rows."""
import pytest

from benchmark.harness import window as w


def test_boundaries_and_warmup():
    assert [r for r in range(30) if w.is_boundary(r, 10)] == [9, 19, 29]
    # the fewest whole cycles that hold ten rounds
    assert w.warmup_rounds(10, 10) == 10
    assert w.warmup_rounds(1, 10) == 10
    assert w.warmup_rounds(4, 10) == 12
    assert w.warmup_rounds(25, 10) == 25     # never less than one cycle


def test_window_closes_on_the_first_whole_cycle_past_the_length():
    # eval_freq 10, a round stamped every 0.5 s, the cycle's last round
    # 3 s later (eval + checkpoint): cycles close at 8, 16, 24, ... s
    stamps, t = {}, 0.0
    for r in range(60):
        t += 0.5 + (3.0 if w.is_boundary(r, 10) else 0.0)
        stamps[r] = t
    open_round = 9                              # one warm-up cycle
    closed = [r for r in range(10, 60) if w.closes_window(
        r, stamps[r], stamps[open_round], 20.0, 10)]
    # 16 s after two cycles is short of 20 s; the third closes at 24 s
    assert closed[0] == 39
    assert stamps[39] - stamps[open_round] == pytest.approx(24.0)


def test_throughput_counts_all_work_over_all_time():
    # 30 rounds of 10 clients x 10 steps x 50 images in 24 s on 1 chip
    assert w.samples_per_s_chip(30, 5000, 24.0, 1) == pytest.approx(6250.0)
    assert w.samples_per_s_chip(30, 5000, 24.0, 4) == pytest.approx(1562.5)
    # the per-layer reader takes the same from a run's context
    from benchmark.layer_metrics import loop_samples_per_s_chip as reader
    ctx = {"rows": [{}] * 30, "samples_per_round": 5000,
           "window": {"seconds": 24.0}, "cell": {"chips": 4}}
    assert reader.read(ctx) == pytest.approx(1562.5)
    assert reader.read(dict(ctx, rows=[])) is None


def test_train_iterations_and_cycles_from_the_callback_stamps():
    # eval_freq 5: rounds 4, 9, 14 close a cycle (evaluation and
    # checkpoint inside their iteration), every other iteration is a
    # train round alone
    stamps, t = {}, 0.0
    for r in range(15):
        t += 0.2 + (1.0 if w.is_boundary(r, 5) else 0.0)
        stamps[r] = t
    walls = w.train_iteration_walls(stamps, 5, 14, 5)
    assert len(walls) == 8 and walls == pytest.approx([0.2] * 8)
    assert w.cycle_walls(stamps, 4, 14, 5) == pytest.approx([2.0, 2.0])
    # a cell that evaluates every round has no such iteration
    assert w.train_iteration_walls(stamps, 5, 14, 1) == []


def test_host_gap_excludes_what_the_launcher_timed():
    rows = [{"round": 10, "round_s": 0.1},
            {"round": 11, "round_s": 0.1, "eval_s": 0.4,
             "checkpoint_s": 2.0}]
    stamps = {9: 100.0, 10: 100.15, 11: 102.75}
    # 0.15 - 0.1 = 0.05 and 2.6 - 2.5 = 0.1: mean 0.075
    assert w.host_gap_s_per_round(stamps, rows, 10, 11) \
        == pytest.approx(0.075)
    assert w.window_rows(rows, 11, 11) == rows[1:]
