"""Round program: the exits' share of their roofline. The least time
the chip could take for a round's sequences of
``flops/<arch>.py:exit_flops`` over the bf16 peak or ``exit_bytes``
over the memory bandwidth, whichever is larger (``R`` heads a
sequence, forward and backward, of the mathematics), over
``round_exit_device_s``. Source: device trace."""
from benchmark.harness import scope_sum


def read(ctx):
    return scope_sum.roofline_pct(ctx, scope_sum.EXITS, "exit")
