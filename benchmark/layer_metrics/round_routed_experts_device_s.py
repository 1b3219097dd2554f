"""Model: device self seconds a traced round of the routed share of the
expert layers of a model that has a shared expert beside it: the scopes
``lm.router`` (the float32 router product, sigmoid, the choice by score
plus bias, the counting sort, the buffer's fill, the load's count) and
``lm.experts`` with the grouped products' ``ragged-dot-*`` custom calls,
as ``round_experts_device_s`` reads them; the shared expert is
``round_shared_expert_device_s``. None where the configuration counts no
shared expert (``flops/<arch>.py`` without ``shared_flops``) or the
program carries no ``lm.experts`` scope. Source: device trace."""
from benchmark.harness import runner
from benchmark.layer_metrics import round_experts_device_s


def read(ctx):
    flops = runner.load_by_name("flops", ctx["cell"]["config_file"]["arch"])
    if not hasattr(flops, "shared_flops"):
        return None
    return round_experts_device_s.read(ctx)
