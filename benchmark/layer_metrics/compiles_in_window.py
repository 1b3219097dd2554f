"""Round program: traces counted by the program's
``RecompilationSentinel`` between the window's first and last callback;
0 when nothing retraced. Source: program counter."""


def read(ctx):
    return float(sum(ctx["traces_in_window"].values()))
