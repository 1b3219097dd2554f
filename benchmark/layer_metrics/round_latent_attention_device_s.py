"""Model: device self seconds a traced round of the latent-attention
layers' causal softmax core: the operations under the scope
``lm.attention`` of a model whose attention is latent (query/key heads
of 192 beside value heads of 128: the flash kernel's forward, its
chunked backward and the forward's recomputation from 4096-token rows
on, the dense form below), inside the round module's executions. None
where the configuration counts no such work (``flops/<arch>.py`` without
``latent_flops``) or the trace holds no such scope. Source: device
trace."""
from benchmark.harness import runner, scope_reduce


def read(ctx):
    flops = runner.load_by_name("flops", ctx["cell"]["config_file"]["arch"])
    if not hasattr(flops, "latent_flops"):
        return None
    return scope_reduce.scope_s_per_round(ctx, "lm.attention")
