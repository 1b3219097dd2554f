"""Round program: the looped stack's share of its roofline. The least
time the chip could take for a round's sequences (k clients x K steps x
B rows) of ``flops/<arch>.py:loop_stack_flops`` over the bf16 peak or
``loop_stack_bytes`` over the memory bandwidth, whichever is larger
(``R x n`` layer calls a sequence, forward and backward, of the
mathematics), over ``round_loop_stack_device_s``. Source: device
trace."""
from benchmark.harness import scope_sum


def read(ctx):
    return scope_sum.roofline_pct(ctx, scope_sum.LOOP_STACK, "loop_stack")
