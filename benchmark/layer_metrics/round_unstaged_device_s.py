"""Round program: device self seconds a traced round of the round
module's operations that carry no stage name (``while`` shells, copies
of arguments the compiler adds; the traced run's log names them). None
where no operation carries one: the program has no scopes. Source:
device trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    red = stage_reduce.get(ctx)
    if not red or not red["stage_s"]:
        return None
    return red["unstaged_s"] / max(red["rounds"], 1)
