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
# flash kernel. From the 2026-07-31 on-chip training A/B at the tuned
# block defaults (FLASH_TRAIN.json, TPU v5e; a hypothesis until the
# ledger repeats it, ROADMAP Speed 5):
# T=1024 1.12x, T=2048 0.68x (a REGRESSION — the dense path's [T, T]
# scores still fit comfortably and the kernel's launch/tiling overhead
# dominates), T=4096 1.77x (outside the noise band), T=8192 1.05x with
# the dense score tensor already at 2.1 GB/layer. Flash is therefore
# the default only where it measurably wins or where dense memory
# becomes the binding constraint — T >= 4096.
FLASH_MIN_SEQ_LEN = 4096


def resolve_attention(mode: str, seq_len: int) -> str:
    """Resolve an attention mode ('auto'|'dense'|'flash') for a static
    sequence length. 'auto' guards users from the measured T=2048
    regression window (constant above); explicit modes pass through so
    A/Bs can pin either backend at any T."""
    if mode == "auto":
        return "flash" if seq_len >= FLASH_MIN_SEQ_LEN else "dense"
    if mode not in ("dense", "flash"):
        raise ValueError(
            f"attention must be 'auto', 'dense' or 'flash', got {mode!r}")
    return mode
