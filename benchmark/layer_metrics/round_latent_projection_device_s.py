"""Model: device self seconds a traced round under the scope
``lm.latent``: all of a latent-attention sublayer but its softmax
attention (the four products ``W_q``, ``W_a``, ``W_b``, ``W_o``, the
latent's norm, the rotary turn and the heads' assembly), forward,
recomputation and backward, inside the round module's executions. None
where the program carries no such scope. Source: device trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.scope_s_per_round(ctx, "lm.latent")
