"""Checkpoint layer: seconds of a checkpoint under the program's
``checkpoint.snapshot`` span (the device's state gathered onto the
host, ``device_get`` of every leaf: the one part of a save that needs
the device's state still, so the floor of a save taken off the loop),
mean over the window's checkpoints. None where the program records no
such span. Source: program span."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    return checkpoint_file_write_s_per_call.mean_seconds(
        ctx, "checkpoint.snapshot")
