"""Checkpoint layer: GiB of payload a checkpoint serializes: the
``bytes`` arg of its ``checkpoint.digest`` span (the frame's payload
length), mean over the window's checkpoints. Every rate of the save's
spans is this over their seconds. None where the program records no
such span. Source: program counter."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    calls = checkpoint_file_write_s_per_call.spans_by_checkpoint(
        ctx, "checkpoint.digest")
    if calls is None:
        return None
    return sum(s[3].get("bytes", 0) for call in calls
               for s in call) / len(calls) / 2 ** 30
