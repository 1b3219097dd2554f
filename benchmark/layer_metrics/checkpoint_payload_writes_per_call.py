"""Checkpoint layer: payload files a checkpoint writes, fsyncs and
renames: its ``checkpoint.file_write`` spans whose ``name`` ends in
``.ckpt`` (the few kB of ``.json`` beside them are not counted), mean
over the window's checkpoints. 1 where every name a save gets beyond
the first is a link, 2 where ``model_best.ckpt`` is written as a file
of its own. None where the program records no such span. Source:
program counter."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    calls = checkpoint_file_write_s_per_call.spans_by_checkpoint(
        ctx, "checkpoint.file_write")
    if calls is None:
        return None
    return sum(str(s[3].get("name", "")).endswith(".ckpt")
               for call in calls for s in call) / len(calls)
