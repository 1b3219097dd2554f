"""``flops/ouro.py`` against counts written out: the looped
configuration's operations and bytes from its published shapes."""
from benchmark.flops import ouro as flops


def test_counts_of_the_configuration_written_out():
    s = flops.spec()
    t = s["seq_len"]
    assert (t, s["total_ut_steps"], len(s["layer_types"])) == (1024, 4, 8)
    assert flops.layer_counts(s) == {"full": 32}
    # a layer's weight matrices: four 2048 x 2048, three 2048 x 5632
    assert flops.layer_matmul_macs_per_token(s) == 4 * 4194304 \
        + 3 * 11534336 == 51380224
    # causal attention of one layer call: 524 800 pairs, QK^T and PV,
    # two FLOPs a multiply-accumulate, forward and twice that backward
    assert flops.attention_flops(t, s) == 12 * 524800 * 2048 \
        == 12897484800
    assert flops.attention_bytes(t, s) == 12 * 1024 * 2048 * 2
    # the stack: 32 layer calls of 6 FLOPs a weight and token, and of
    # the attention core
    assert flops.loop_stack_flops(t, s) == 32 * (
        6 * 51380224 * 1024 + 12897484800) == 10514482593792
    # the exits: four heads of 2048 x 49152
    assert flops.exit_flops(t, s) == 4 * 6 * 2048 * 49152 * 1024 \
        == 2473901162496
    assert flops.train_flops_per_image(s) == 10514482593792 \
        + 2473901162496 == 12988383756288
    # a layer call moves its float32 weights three times and the
    # residual stream five; an exit its head three times, the stream two
    assert flops.loop_stack_bytes(t, s) == 32 * (
        3 * 51380224 * 4 + 5 * 1024 * 2048 * 4)
    assert flops.exit_bytes(t, s) == 4 * (
        3 * 2048 * 49152 * 4 + 2 * 1024 * 2048 * 4)
    # at the 2048 tokens ISSUE 35 first named: 26.8 TFLOP a sequence
    assert flops.loop_stack_flops(2048, s) + flops.exit_flops(2048, s) \
        == 26801401233408


def test_both_pieces_are_bound_by_the_products_at_the_cells_length():
    """At the cell's 1024 tokens the least time of the stack and of the
    exits is their FLOPs over the peak, not their bytes over the
    bandwidth (twice over; the attention core alone is near the ridge):
    the roofline shares read against the MXU."""
    s = flops.spec()
    t = s["seq_len"]
    for work, margin in (("loop_stack", 2.0), ("exit", 2.0),
                         ("attention", 1.0)):
        f = getattr(flops, work + "_flops")(t, s) / 197e12
        b = getattr(flops, work + "_bytes")(t, s) / 819e9
        assert f > margin * b, (work, f, b)
    # a round of the cell: 4 sequences, 52.0 TFLOP
    assert round(4 * flops.train_flops_per_image(s) / 1e12, 1) == 52.0
