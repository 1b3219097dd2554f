"""The device stamp, and the refusal to measure without a chip.

Every record a measurement entry point writes names the device it ran
on, as JAX reports it. An entry point whose numbers only mean something
on the accelerator (chip_smoke.py)
calls :func:`require_tpu` before it compiles anything: with no TPU it
exits non-zero instead of carrying on on the CPU.

This initialises the backend in the calling process, which then holds
the chip: call it from the one process that does the work, never from
a parent that goes on to spawn chip-using children.
"""
from __future__ import annotations


def device_stamp() -> dict:
    """``{"platform", "kind", "count"}`` of JAX's default backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu(what: str) -> dict:
    """The device stamp when the default platform is ``tpu``; otherwise
    ``SystemExit`` (exit code 1) naming what refused and what JAX found."""
    stamp = device_stamp()
    if stamp["platform"] != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU, but JAX's default platform is "
            f"{stamp['platform']!r} ({stamp['kind']} x {stamp['count']}). "
            "A CPU run yields no device number; refusing to continue.")
    return stamp
