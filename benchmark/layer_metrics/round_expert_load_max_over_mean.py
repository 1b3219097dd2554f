"""Model: how unevenly the round's tokens load the experts held: the
launcher's own counter on the round's row,
``lm_moe_load_max_over_mean`` (the fullest held expert's token-expert
pairs over the held experts' mean, a row-step and layer, mean over the
round's clients, steps and layers; 1 is even), the window's median.
None where the rows carry no such counter. Source: program counter."""
import statistics


def read(ctx):
    loads = [r["lm_moe_load_max_over_mean"] for r in ctx["rows"]
             if "lm_moe_load_max_over_mean" in r]
    return statistics.median(loads) if loads else None
