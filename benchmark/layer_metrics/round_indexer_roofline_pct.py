"""Model: the indexer's share of its roofline. The least time the chip
could take for a round's layer calls (k clients x K steps x B rows x
the configuration's layers) of ``flops/<arch>.py:indexer_flops`` over
the bf16 peak or ``indexer_bytes`` over the memory bandwidth, whichever
is larger (the three projections and the scores over the causal pairs,
forward and backward, of the mathematics: the threshold's search is
not in it), over ``round_indexer_device_s``. Source: device trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.mixer_roofline_pct(ctx, "lm.indexer", "full",
                                           "indexer")
