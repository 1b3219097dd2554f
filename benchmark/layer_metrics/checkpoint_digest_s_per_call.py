"""Checkpoint layer: seconds of a checkpoint under the program's
``checkpoint.digest`` span (the payload's sha256, fed piece by piece),
mean over the window's checkpoints. None where the program records no
such span. Source: program span."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    return checkpoint_file_write_s_per_call.mean_seconds(
        ctx, "checkpoint.digest")
