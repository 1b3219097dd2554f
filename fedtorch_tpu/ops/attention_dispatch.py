"""Attention backend dispatch policy — pallas-free on purpose.

The policy is a pure string/int decision, but it used to live next to
the flash kernel, whose module imports ``jax.experimental.pallas``
at top level — so the DENSE path (which never runs the kernel) would
still crash at import time on jax builds without pallas/Mosaic.
Keeping the dispatch here lets ``models/transformer.py`` resolve the
backend without touching the kernel stack; the kernel module
re-exports these names for callers that already import them from
there.
"""
from __future__ import annotations

# Shortest sequence length at which 'auto' attention dispatch picks the
# flash kernel. Set from a capture of 2026-07-31 on a v5e, before PR 1
# (record removed in PR 29): flash slower than dense at T=2048 (the
# dense [T, T] scores still fit and the kernel's launch and tiling
# overhead dominates) and faster from T=4096, where the dense score
# tensor also starts to bind memory (2.1 GB a layer at T=8192).
# Read on today's code by PR 33 (PERF.md section 5; one chip, forward,
# recomputation and backward of a layer call, 30 heads of 128): the
# flash path at T=4096 21.5 ms a call, 9.1 % of causal attention's
# roofline (the Mosaic forward and its chunked float32 XLA backward);
# the dense path at T=2048 6.7 ms a call, 7.3 %. Neither length ran
# both paths, so the constant stays where the capture put it. The
# looped cell of PR 35 (16 heads of 128, T=1024) reads the dense path
# 32 times a step: its seconds are in PERF.md section 5. The selected
# layers of PR 39's cell (32 query on 4 key heads of 128, 2048 keys a
# query) take neither path: the flash kernel has no mask argument and
# returns no probabilities, so ``ops/sparse_attention.py`` has its own
# two forms, whatever this constant says. Since PR 40, on a TPU where
# the shapes tile, the fused kernels of
# ``ops/pallas/selected_attention.py`` (mask from the indexer's
# threshold, online softmax, the heads' summed probabilities, a backward
# kernel; bfloat16 operands to the MXU): T=4096 6.4 ms a layer call
# (forward, the target's sweep twice, backward), 24.7 % of the SELECTED
# pairs' roofline; T=8192 22.1 ms, 16.6 % (builder, PR 40). Elsewhere masked dense query chunks
# of 512, which read there (one chip, forward, recomputation and
# backward of a layer call, PR 39): T=4096 19.8 ms a call, 7.9 %;
# T=6144 46.4 ms, 5.6 %; T=8192 91.7 ms, 4.0 %.
# The latent-attention layers of PR 41's cell (32 heads, 192 wide for
# queries and keys and 128 for values) take this dispatch as any
# full-attention layer does: the flash kernel has a value head size of
# its own since then (nothing padded), and the dense form never had
# one. Since PR 42 the flash path is a kernel in both directions
# (causal tiles only, the arrays' own type to the MXU, no row of scores
# in HBM). At T=4096 at those heads, one chip, one row, bfloat16, by
# hand (builder, PR 42, 2026-10-03): forward 2.38 ms (36.7 % of the
# MXU's peak on the causal pairs' 0.172 TFLOP), backward 4.56 ms
# (49.8 % on 0.447 TFLOP; the chunked float32 XLA scan it replaced
# 21.8), forward and backward with the layout copies 6.85 (PR 41:
# 25.1, against 29.6 dense; forward alone 3.7 against 16.1 dense); the
# cell's traced round reads 8.4 ms a layer call with the recomputed
# forward (PR 41: 27.9), 31.3 % of causal attention's roofline (9.4).
# At 30 heads of 128 (the olmo file's, T=4096): forward 1.42, backward
# 2.52 (the scan 15.3). The dense path below 4096 was not read again.
FLASH_MIN_SEQ_LEN = 4096


def on_tpu() -> bool:
    """Whether the program is being built for a TPU: what decides, with
    the shapes, that the selected layers run their fused kernels
    (``ops/sparse_attention.py``: ``takes_kernel``) and that those are
    compiled, not interpreted."""
    import jax
    return jax.default_backend() == "tpu"


def resolve_attention(mode: str, seq_len: int) -> str:
    """Resolve an attention mode ('auto'|'dense'|'flash') for a static
    sequence length. 'auto' keeps short sequences on the dense path
    (constant above); explicit modes pass through so
    A/Bs can pin either backend at any T."""
    if mode == "auto":
        return "flash" if seq_len >= FLASH_MIN_SEQ_LEN else "dense"
    if mode not in ("dense", "flash"):
        raise ValueError(
            f"attention must be 'auto', 'dense' or 'flash', got {mode!r}")
    return mode
