"""Model: device self seconds a traced round of the operations whose
framework name holds ``rematted_computation``, inside the round
module's executions: what the backward pass of a ``jax.checkpoint``
runs again of the forward pass (JAX's own name for it, whatever
``lm.*`` scope the operation lies in: the layers' checkpoints, the
looped model's exits, the delta rule's scan body). Where a
rematerialized layer keeps its matrix products' results
(``models/hybrid_lm.py``: ``kept_products``) their seconds leave this
number; the norms, gates, convolution, rotary turn, softmax and delta
rule stay in it. None without a trace, and where the program runs
nothing again. Source: device trace."""
from benchmark.harness import tag_reduce


def read(ctx):
    return tag_reduce.tagged_s_per_round(ctx, "rematted_computation")
