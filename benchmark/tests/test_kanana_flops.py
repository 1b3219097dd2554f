"""``flops/kanana2.py`` against counts written out: the latent-attention,
shared-expert configuration's parameters, pairs, operations and bytes
from its published shapes and its stated share (ISSUE 41's
arithmetic)."""
from benchmark.flops import kanana2 as flops


def test_counts_of_the_configuration_written_out():
    s = flops.spec()
    assert (s["seq_len"], s["num_hidden_layers"], s["n_routed_experts"],
            s["routed_experts"], s["first_k_dense_replace"]) == (
        4096, 5, 16, 128, 1)
    t = 4096
    assert flops.layer_counts(s) == {"latent": 5, "dense": 1, "expert": 4}
    # a layer's attention: W_q, W_a, W_b, W_o (the latent's norm apart)
    assert flops.latent_params(s) == 12582912 + 1179648 + 4194304 + 8388608 \
        == 26345472 == s["parameters"]["attention"] - 512
    assert flops.expert_params(s) == 3 * 2048 * 768 == 4718592
    assert flops.shared_params(s) == 9437184 == s["parameters"][
        "shared_expert"]
    assert flops.dense_params(s) == 37748736 == s["parameters"][
        "dense_feed_forward"]
    assert s["parameters"]["total"] == 64098816 + 4 * 111547008 \
        + 2 * 32833536 + 2048 == 575955968
    # 8.39 M causal pairs; 3072 token-expert pairs expected on the 16
    # experts held, 192 an expert
    assert flops.causal_pairs(t) == 8390656
    assert flops.expected_pairs(t, s) == 4096 * 6 * 16 / 128 == 3072.0
    # forward multiply-accumulates a token: 255.2 M (ISSUE 41)
    macs = 5 * 26345472 + 37748736 + 4 * (262144 + 9437184 + 0.75 * 4718592) \
        + 2048 * 16032
    assert round(macs / 1e6, 1) == 255.3
    # a causal pair of a head: 2 x 192 of q k^T and 2 x 128 of p v
    assert flops.attention_flops(t, s) == 6 * 8390656 * (6144 + 4096) \
        == 3 * 8390656 * 32 * 640
    assert flops.latent_flops(t, s) == 6 * 4096 * 26345472
    assert flops.shared_flops(t, s) == 6 * 4096 * 9437184
    assert flops.dense_flops(t, s) == 6 * 4096 * 37748736
    assert flops.experts_flops(t, s) == 3 * (
        2 * 4096 * 2048 * 128 + 2 * 3072 * 4718592)
    # the pairs a run counted take the expected ones' place
    assert flops.experts_flops(t, s, 4096) - flops.experts_flops(t, s) \
        == 6 * 1024 * 4718592
    assert flops.head_flops(t, s) == 6 * 4096 * 2048 * 16032
    total = flops.train_flops_per_image()
    assert total == 5 * (flops.latent_flops(t, s)
                         + flops.attention_flops(t, s)) \
        + flops.dense_flops(t, s) + 4 * (flops.experts_flops(t, s)
                                         + flops.shared_flops(t, s)) \
        + flops.head_flops(t, s)
    # ISSUE 41: 6.3 TFLOP of products and 2.6 of causal attention a step
    assert round((total - 5 * flops.attention_flops(t, s)) / 1e12, 1) == 6.3
    assert round(5 * flops.attention_flops(t, s) / 1e12, 1) == 2.6
    # q, k at 192 and v, o at 128 a head: 2 + 2 in and out forward;
    # q k v o do in and three cotangents out backward
    qk, v = 4096 * 6144 * 2, 4096 * 4096 * 2
    assert flops.attention_bytes(t, s) == (2 * qk + 2 * v) \
        + (2 * qk + 3 * v) + (2 * qk + v)


def test_what_bounds_each_piece_at_the_cells_length():
    """Causal attention at 4096 tokens is bound by the MXU (its bytes
    would take a quarter of the time); the latent projections and the
    shared expert too; the routed share by its weights' bytes."""
    s = flops.spec()
    t, peak, bw = 4096, 197e12, 819e9
    assert flops.attention_flops(t, s) / peak \
        > 4 * flops.attention_bytes(t, s) / bw
    assert flops.latent_flops(t, s) / peak > flops.latent_bytes(t, s) / bw
    assert flops.shared_flops(t, s) / peak > flops.shared_bytes(t, s) / bw
    assert flops.experts_flops(t, s) / peak < flops.experts_bytes(t, s) / bw
