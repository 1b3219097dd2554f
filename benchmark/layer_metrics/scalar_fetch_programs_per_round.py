"""Entry layer: device program executions, other than the round
program's, that start inside a ``round.wait`` or ``scalar_fetch``
annotation of the profile, per traced round: how many small programs
the one batched fetch dispatches before it transfers (the loop
dispatches nothing else there, and the device's clock runs a
millisecond or two ahead of the host's, so the first of them start
under the wait's end). None where the profile holds no annotation.
Source: device trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    red = stage_reduce.get(ctx)
    if not red or not red["annotated"]:
        return None
    runs = sum(p["runs"] for p in red["fetch_programs"].values())
    return runs / max(red["rounds"], 1)
