"""Model: device self seconds a traced round of the selection's
indexer: the operations under the scope ``lm.indexer`` (its three
projections, the scores, the threshold's search and the selection's
mask, the KL term), forward, recomputation and backward, inside the
round module's executions. None where the program carries no such
scope. Source: device trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.scope_s_per_round(ctx, "lm.indexer")
