"""Model: the share of the round's latent-attention layer calls whose
backward pass ran the flash path's backward kernel
(``ops/pallas/flash_attention.py``: a tile of scores in VMEM alone,
causal tiles only) and not its chunked scan in plain XLA: the
launcher's own counter on the round's row,
``lm_attention_backward_kernel_share`` (0 to 1, the backward rule's own
decision when the round is traced), the window's median. None where the
rows carry no such counter (a program without the kernel has none).
Source: program counter."""
import statistics


def read(ctx):
    shares = [r["lm_attention_backward_kernel_share"] for r in ctx["rows"]
              if "lm_attention_backward_kernel_share" in r]
    return statistics.median(shares) if shares else None
