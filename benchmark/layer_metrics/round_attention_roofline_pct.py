"""Round program: causal softmax attention's share of its roofline.
The least time the chip could take for a round's calls (k clients x K
steps x the full-attention layers, forward and backward: the larger of
``flops/olmo_hybrid.py:attention_flops`` over the bf16 peak and
``attention_bytes`` over the memory bandwidth, of the causal
mathematics) over ``round_attention_device_s``. Source: device
trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.mixer_roofline_pct(ctx, "lm.attention", "full",
                                           "attention")
