"""Checkpoint layer: seconds of a checkpoint under its
``checkpoint.file_write.fsync`` spans (each file's ``fsync``, the
payload's and the metas'), summed over the checkpoint's files, mean
over the window's checkpoints. None where the program records no such
span. Source: program span."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    return checkpoint_file_write_s_per_call.mean_seconds(
        ctx, "checkpoint.file_write.fsync")
