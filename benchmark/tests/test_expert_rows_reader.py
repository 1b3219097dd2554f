"""``round_expert_rows_visited_over_pairs`` on made-up rows: the
window's median of the rounds' ``lm_moe_rows_visited /
lm_moe_pairs_local``; None on a parent's rows (no such counter), on no
rows, and where no pair was routed here."""
import os

import pytest

from benchmark.harness import runner

NAME = "round_expert_rows_visited_over_pairs"


def read(rows):
    return runner.load_by_name("layer_metrics", NAME).read({"rows": rows})


@pytest.mark.parametrize("visited,pairs,want", [
    # one trip of 6144 rows a layer call, whatever was routed here
    ([6144.0, 6144.0, 6144.0], [4096.0, 3840.0, 4608.0], 1.5),
    # some layer calls of a round took a second trip: the mean is not
    # a multiple of the block
    ([6144.0, 7680.0, 9216.0], [4096.0, 5120.0, 6144.0], 1.5),
    # the whole buffer, as the program before the blocks ran it
    ([32768.0], [4096.0], 8.0),
    ([4608.0, 4608.0], [3072.0, 2304.0], 1.75),
])
def test_the_windows_median_of_the_rounds_ratios(visited, pairs, want):
    rows = [{"round": i, "round_s": 0.6, "lm_moe_rows_visited": v,
             "lm_moe_pairs_local": p}
            for i, (v, p) in enumerate(zip(visited, pairs))]
    assert read(rows) == pytest.approx(want)


@pytest.mark.parametrize("rows", [
    [],
    # the parent's rows: pairs and load, no rows visited
    [{"round": 0, "round_s": 0.74, "lm_moe_pairs_local": 4100.0,
      "lm_moe_load_max_over_mean": 4.0}],
    # none routed here: nothing to divide by
    [{"round": 0, "lm_moe_rows_visited": 6144.0,
      "lm_moe_pairs_local": 0.0}],
    # a model without experts
    [{"round": 0, "round_s": 0.6, "tokens_trained": 8192.0}],
])
def test_none_where_there_is_nothing_to_read(rows):
    assert read(rows) is None


def test_the_benchmark_lists_it_for_the_two_sparse_cells():
    listed = {m["name"]: m for m in runner.load_json(
        os.path.join(runner.REPO, "BENCHMARK.json"))["per_layer"]}
    assert listed[NAME]["workloads"] == [
        "keye_vl2_30b_a3b_l4.fedavg_k2_e10",
        "kanana2_30b_a3b_l5.fedavg_k2_e10"]
    assert listed[NAME]["source"] == "program_counter"
    assert listed[NAME]["moves"] == "round_s_p50"
