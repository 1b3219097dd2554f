"""Model: the share of the round's latent-attention layer calls whose
softmax attention ran the flash kernel
(``ops/pallas/flash_attention.py``) and not the dense form: the
launcher's own counter on the round's row, ``lm_attention_kernel_share``
(0 to 1, from the attention mode, the rows' length and the backend when
the round is traced: 'auto' takes the kernel from 4096 tokens on), the
window's median. None where the rows carry no such counter. Source:
program counter."""
import statistics


def read(ctx):
    shares = [r["lm_attention_kernel_share"] for r in ctx["rows"]
              if "lm_attention_kernel_share" in r]
    return statistics.median(shares) if shares else None
