"""Model: how unevenly the round's tokens load ALL the router's experts,
held here or not: the launcher's own counter on the round's row,
``lm_router_load_max_over_mean`` (the fullest expert's token-expert
pairs over the mean of all routed experts, this chip's tokens; a
row-step and layer, mean over the round's clients, steps and layers; 1
is even), the window's median: what the router's selection bias
(``topk_method`` ``noaux_tc``) works against. None where the rows carry
no such counter. Source: program counter."""
import statistics


def read(ctx):
    loads = [r["lm_router_load_max_over_mean"] for r in ctx["rows"]
             if "lm_router_load_max_over_mean" in r]
    return statistics.median(loads) if loads else None
