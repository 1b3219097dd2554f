"""Checkpoint layer: seconds of a checkpoint under the program's
``checkpoint.layout`` span (the payload's pieces laid out: each
strided leaf copied once into C order, the seconds inside those copies
being its arg ``relaid_s``), mean over the window's checkpoints. None
where the program records no such span. Source: program span."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    return checkpoint_file_write_s_per_call.mean_seconds(
        ctx, "checkpoint.layout")
