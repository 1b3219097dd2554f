"""Round program: the delta rule's share of its roofline. The least
time the chip could take for a round's calls (k clients x K steps x
the linear-attention layers, forward and backward: the larger of
``flops/olmo_hybrid.py:delta_rule_flops`` over the bf16 peak and
``delta_rule_bytes`` over the memory bandwidth, both of the
mathematics and not of the chunking) over ``round_delta_rule_device_s``.
Source: device trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.mixer_roofline_pct(ctx, "lm.delta_rule", "linear",
                                           "delta_rule")
