"""Round program: device self seconds a traced round of a looped
model's exits: the operations under the scopes ``lm.exit`` (per-exit
losses, the gate, the exit distribution and the mixture) and
``lm.head`` (the head's products, one exit at a time), forward,
recomputation and backward, inside the round module's executions.
None where the program carries no ``lm.exit`` scope. Source: device
trace."""
from benchmark.harness import scope_sum


def read(ctx):
    return scope_sum.seconds_per_round(ctx, scope_sum.EXITS)
