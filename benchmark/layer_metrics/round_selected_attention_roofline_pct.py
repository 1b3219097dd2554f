"""Model: the selected attention's share of its roofline. The least
time the chip could take for a round's layer calls of
``flops/<arch>.py:selected_attention_flops`` over the bf16 peak or
``selected_attention_bytes`` over the memory bandwidth, whichever is
larger (``q k^T`` and ``p v`` over the SELECTED pairs, forward and
backward), over ``round_selected_attention_device_s``: how far the
masked dense chunks are from the mathematics. Source: device trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.mixer_roofline_pct(ctx, "lm.attention", "full",
                                           "selected_attention")
