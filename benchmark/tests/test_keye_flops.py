"""``flops/keye_vl2.py`` against counts written out: the sparse
configuration's parameters, pairs, operations and bytes from its
published shapes and its stated share."""
from benchmark.flops import keye_vl2 as flops


def test_counts_of_the_configuration_written_out():
    s = flops.spec()
    assert (s["seq_len"], s["num_hidden_layers"], s["num_experts"],
            s["routed_experts"]) == (4096, 4, 16, 128)
    # ISSUE 39's counts are of 8192-token rows, the length it asked for
    # (the cell runs 4096 by its ladder: the second test)
    t = 8192
    assert flops.layer_counts(s) == {"full": 4}
    # the parameter counts of ISSUE 39
    assert flops.indexer_params(s) == 2097152 + 131072 + 32768 == 2260992
    assert flops.expert_params(s) == 3 * 2048 * 768 == 4718592
    assert s["parameters"]["layer"] == 2 * 8388608 + 2 * 1048576 + 256 \
        + 4096 + 2260992 + 262144 + 16 * 4718592 == 96899328
    assert s["parameters"]["total"] == 4 * 96899328 + 2 * 38895616 + 2048 \
        == 465390592
    # the pairs: 2048 x 2049 / 2 while every key is taken, then 2048 a
    # query; 33.6 M causal ones; 8192 token-expert pairs expected here
    assert flops.selected_pairs(t, s) == 2098176 + 6144 * 2048 == 14681088
    assert flops.causal_pairs(t) == 33558528
    assert round(flops.selected_pairs(t, s) / flops.causal_pairs(t), 4) \
        == 0.4375
    assert flops.expected_pairs(t, s) == 8192 * 8 * 16 / 128 == 8192.0
    assert flops.selected_pairs(2048, s) == flops.causal_pairs(2048)
    # a layer call, forward and backward: four projections of
    # 2048 x (4096 + 512 + 512 + 4096)
    assert flops.projection_flops(t, s) == 6 * 8192 * 2048 * 9216 \
        == 927712935936
    # the indexer: projections forward and the weights' product backward
    # (its input is under stop_gradient), scores three times
    assert flops.indexer_flops(t, s) == 4 * 8192 * 2260992 \
        + 6 * 33558528 * 1024 == 280271781888
    # q k^T and p v over the selected pairs of 32 heads of 128
    assert flops.selected_attention_flops(t, s) == 12 * 14681088 * 4096 \
        == 721604837376
    # the router over 128 and three products of 8192 expected pairs
    assert flops.experts_flops(t, s) == 3 * (
        2 * 8192 * 2048 * 128 + 2 * 8192 * 4718592) == 244813135872
    # the pairs a run counted take the expected ones' place
    assert flops.experts_flops(t, s, 16384) - flops.experts_flops(t, s) \
        == 6 * 8192 * 4718592
    assert flops.head_flops(t, s) == 6 * 8192 * 2048 * 18992
    assert flops.train_flops_per_image(dict(s, seq_len=t)) == 4 * (
        927712935936 + 280271781888 + 721604837376 + 244813135872) \
        + 1911797317632 == 10609408081920
    assert flops.selected_attention_bytes(t, s) == 6 * 8192 * 4608 * 2
    assert flops.experts_bytes(t, s) == 3 * 4 * (
        2048 * 128 + 16 * 4718592) + 4 * 8192 * (
        2 * 2 * 2048 + 2 * 2 * 768 + 4 * 2048)


def test_what_bounds_each_piece_at_the_cells_length():
    """At the cell's 4096 tokens three of four causal pairs are
    selected (2048 x 2049 / 2 while every key is taken, then 2048 a
    query); the indexer and the selected attention are bound by their
    FLOPs over the peak (3.5 and 5.7 times their bytes over the
    bandwidth); the expert layers by their bytes (the 16 experts'
    float32 matrices moved three times for 4096 pairs: 1.5 ms against
    0.6 of products), which is what an eighth of the deployment's
    expert load does. A round of the cell is 19.6 TFLOP."""
    s = flops.spec()
    t = s["seq_len"]
    assert flops.selected_pairs(t, s) == 2098176 + 2048 * 2048 == 6292480
    assert flops.causal_pairs(t) == 8390656
    assert flops.expected_pairs(t, s) == 4096.0
    least = lambda work: (
        getattr(flops, work + "_flops")(t, s) / 197e12,
        getattr(flops, work + "_bytes")(t, s) / 819e9)
    f, b = least("indexer")
    assert f > 3 * b
    f, b = least("selected_attention")
    assert f > 5 * b
    f, b = least("experts")
    assert 2.2 * f < b < 2.6 * f
    assert flops.train_flops_per_image(s) == 4892487843840
    assert round(4 * flops.train_flops_per_image(s) / 1e12, 1) == 19.6
