"""Round program: seconds per round in which an operation ran on the
device inside the round program's executions, from the profiler trace
(interval union, averaged over the chips). Source: device trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("round_module"):
        return None
    return t["round_module"]["busy_s"] / max(t["round_module"]["runs"], 1)
