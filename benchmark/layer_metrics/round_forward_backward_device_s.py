"""Round program: device self seconds a traced round under
``fed.local_steps`` whose innermost scope is ``fed.forward_backward``
(loss, gradient, and what the compiler fuses into them: on the v5e the
SGD update rides the weight-gradient convolution). Source: device
trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.local_s_per_round(ctx, "fed.forward_backward")
