"""Model: device self seconds a traced round of the attention over the
selected keys: the operations under the scope ``lm.attention`` of a
model whose file has ``sa_config`` (the masked attention of the query
chunks and the heads' summed probabilities), forward, recomputation
and backward, inside the round module's executions. None where the
configuration counts no such work (``flops/<arch>.py`` without
``selected_attention_flops``) or the trace holds no such scope.
Source: device trace."""
from benchmark.harness import runner, scope_reduce


def read(ctx):
    flops = runner.load_by_name("flops", ctx["cell"]["config_file"]["arch"])
    if not hasattr(flops, "selected_attention_flops"):
        return None
    return scope_reduce.scope_s_per_round(ctx, "lm.attention")
