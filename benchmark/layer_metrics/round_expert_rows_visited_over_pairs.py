"""Model: how much of the expert layers' dispatch buffer the work runs
over, beside the pairs it holds: the launcher's own counters on the
round's row, ``lm_moe_rows_visited`` (the buffer's rows a layer call's
fill, grouped products, activation and combine ran over: the row block
of ``ops/routed_experts.py`` times its trips) over
``lm_moe_pairs_local`` (the token-expert pairs routed to the experts
held), each a mean over the round's clients, steps and layers; the
window's median of the rounds' ratios. 1 is work over the pairs alone;
a program that runs over the whole buffer of ``tokens x per_token``
rows would read ``routed / held`` (8 in both sparse cells) and carries
no such counter. None where the rows carry none, or no pair was routed
here. Source: program counter."""
import statistics


def read(ctx):
    ratios = [r["lm_moe_rows_visited"] / r["lm_moe_pairs_local"]
              for r in ctx["rows"]
              if "lm_moe_rows_visited" in r and r.get("lm_moe_pairs_local")]
    return statistics.median(ratios) if ratios else None
