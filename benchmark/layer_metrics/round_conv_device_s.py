"""Round program: device self seconds a traced round of the round
module's operations that hold a convolution or a dot, by the trace's
HLO category: the MXU's work apart from the pointwise fusions the trace
names alike. None where the trace gives no category. Source: device
trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    red = stage_reduce.get(ctx)
    if not red or red["conv_s"] is None:
        return None
    return red["conv_s"] / max(red["rounds"], 1)
