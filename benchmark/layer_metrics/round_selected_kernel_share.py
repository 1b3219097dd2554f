"""Model: the share of the round's selected-attention layer calls that
ran the fused kernels (``ops/pallas/selected_attention.py``: the scores
of a tile in VMEM alone) and not the masked dense chunks: the
launcher's own counter on the round's row, ``lm_selected_kernel_share``
(0 to 1, from the backend and shapes when the round is traced;
``ops/sparse_attention.py``: ``takes_kernel``), the window's median.
None where the rows carry no such counter (a program without the
kernels, or a model that selects no keys, has none). Source: program
counter."""
import statistics


def read(ctx):
    shares = [r["lm_selected_kernel_share"] for r in ctx["rows"]
              if "lm_selected_kernel_share" in r]
    return statistics.median(shares) if shares else None
