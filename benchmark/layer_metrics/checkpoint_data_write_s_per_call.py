"""Checkpoint layer: seconds of a checkpoint under its
``checkpoint.file_write.data`` spans (each file's open, ``write`` loop
and ``flush``; one an attempt, so a retried write counts twice),
summed over the checkpoint's files, mean over the window's
checkpoints. None where the program records no such span. Source:
program span."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    return checkpoint_file_write_s_per_call.mean_seconds(
        ctx, "checkpoint.file_write.data")
