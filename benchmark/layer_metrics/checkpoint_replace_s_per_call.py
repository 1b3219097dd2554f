"""Checkpoint layer: seconds of a checkpoint spent putting its files
in the place of the ones before: its ``checkpoint.file_write.rename``
spans (``os.replace`` of each finished file over the last save's: the
old file's blocks are freed in here) plus its ``checkpoint.link`` spans
(the best model's hard link, made over the old one), summed over the
checkpoint's files, mean over the window's checkpoints. None where the
program records no ``checkpoint.file_write.rename`` span. Source:
program span."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    renames = checkpoint_file_write_s_per_call.spans_by_checkpoint(
        ctx, "checkpoint.file_write.rename")
    if renames is None:
        return None
    links = checkpoint_file_write_s_per_call.spans_by_checkpoint(
        ctx, "checkpoint.link") or []
    return sum(s[2] for call in renames + links
               for s in call) / len(renames)
