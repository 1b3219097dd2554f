"""Checkpoint layer: the share of a checkpoint's leaf bytes that its
serializer emitted as views of the snapshot's own arrays, with no copy
at all: ``borrowed_bytes / (borrowed_bytes + relaid_bytes +
copied_bytes)`` of the ``checkpoint.serialize`` spans of the window's
checkpoints (``relaid_bytes``: leaves copied once into C order because
their memory lies in another; ``copied_bytes``: leaves flax's packer
copied). 1 where every leaf is borrowed; a tree that takes a slower way
reads lower where it would otherwise hide. None where the span holds no
such attributes (a program that builds the payload as one object) or
there is no such span. Source: program counter."""
from benchmark.layer_metrics import checkpoint_file_write_s_per_call


def read(ctx):
    calls = checkpoint_file_write_s_per_call.spans_by_checkpoint(
        ctx, "checkpoint.serialize")
    if calls is None:
        return None
    noted = [s[3] for call in calls for s in call
             if "borrowed_bytes" in s[3]]
    borrowed = sum(a["borrowed_bytes"] for a in noted)
    total = borrowed + sum(a.get("relaid_bytes", 0)
                           + a.get("copied_bytes", 0) for a in noted)
    return borrowed / total if total else None
