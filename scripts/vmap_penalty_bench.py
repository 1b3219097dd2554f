"""Quantify the cost of per-client weights in the federated hot loop.

Federated local training gives every online client its OWN parameters, so
the round program vmaps the train step over a [k] client axis of weights:
XLA lowers the convolutions with ``batch_group_count=k`` (grouped conv)
instead of one large dense conv. This script measures that penalty on the
current backend by timing a single fwd+bwd train step three ways on
identical total work (k*B images):

  shared   — one conv batch of k*B images, one weight set (the ceiling:
             what a non-federated data-parallel step would cost)
  vmapped  — vmap over k clients with k weight sets (the federated round's
             actual shape)
  scanned  — lax.scan over the k clients (serialized small batches)

The gap between `shared` and `vmapped` is the price of federated
semantics, not implementation slack; `scanned` shows the alternative the
engine rejected.

Second section (``conv_lowering``): per-stage micro A/B of HOW the
per-client conv lowers. vmap-of-conv with a [k] weight axis becomes a
``batch_group_count=k`` grouped convolution; the alternative
formulation extracts im2col patches and runs one batched matmul
``[k, B·P, 9C] x [k, 9C, F]`` — rows/cols the MXU tiles directly. If
the matmul form wins decisively on fwd+bwd, a model-level opt-in conv
path is the next MFU lever; if not, the grouped-conv lowering is
already fine and the MFU ceiling is the channel underfill documented
in docs/performance.md.  Writes VMAP_PENALTY.json.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from fedtorch_tpu.config import (  # noqa: E402
    DataConfig, ExperimentConfig, FederatedConfig, MeshConfig, ModelConfig,
    OptimConfig,
)
from fedtorch_tpu.models import define_model  # noqa: E402
from fedtorch_tpu.utils import enable_compile_cache  # noqa: E402

K_CLIENTS, BATCH = 10, 50
STEPS = 20


def build_model(dtype="bfloat16"):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="cifar10", batch_size=BATCH),
        federated=FederatedConfig(federated=True, num_clients=K_CLIENTS),
        model=ModelConfig(arch="resnet20"),
        optim=OptimConfig(lr=0.1),
        mesh=MeshConfig(compute_dtype=dtype),
    ).finalize()
    return define_model(cfg, batch_size=BATCH)


def timeit(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(STEPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / STEPS


def conv_lowering_ab():
    """Per-resnet20-stage fwd+bwd timing: vmapped conv (grouped-conv
    lowering) vs im2col + batched matmul (same math, MXU-native
    shape). Patch extraction is charged to the matmul variant — it is
    part of that formulation's real cost."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.RandomState(1)
    dt = jnp.bfloat16
    section = {}
    for cin, cout, hw in ((16, 16, 32), (32, 32, 16), (64, 64, 8)):
        x = jnp.asarray(rng.randn(K_CLIENTS, BATCH, hw, hw, cin), dt)
        w = jnp.asarray(rng.randn(K_CLIENTS, 3, 3, cin, cout) * 0.05,
                        dt)

        def conv_one(xi, wi):
            return lax.conv_general_dilated(
                xi, wi, window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        def loss_conv(w_):
            return jnp.sum(jax.vmap(conv_one)(x, w_) ** 2)

        def loss_matmul(w_):
            # [k, B, hw, hw, 9*cin] patches; charged to this variant
            patches = jax.vmap(lambda xi: lax.conv_general_dilated_patches(
                xi, (3, 3), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")))(x)
            p = patches.reshape(K_CLIENTS, BATCH * hw * hw, 9 * cin)
            # conv_general_dilated_patches orders features as
            # [cin, 3, 3]; permute the weights to match
            wm = w_.transpose(0, 3, 1, 2, 4).reshape(
                K_CLIENTS, cin * 9, cout)
            return jnp.sum(jnp.einsum("kpc,kcf->kpf", p, wm) ** 2)

        # numerics agreement guard (bf16 tolerance) before timing
        a = jax.jit(loss_conv)(w)
        b = jax.jit(loss_matmul)(w)
        rel = abs(float(a) - float(b)) / max(abs(float(a)), 1e-9)
        row = {"agree_rel_err": round(rel, 4)}
        if rel > 0.05:  # bf16 tolerance — ENFORCED, not just recorded
            row["invalid"] = ("formulations disagree; timing skipped "
                              "(patch ordering regression?)")
            print(f"conv_lowering {cin}->{cout}: DISAGREE rel={rel:.3f}"
                  " — skipping timings", file=sys.stderr)
            section[f"stage_{cin}x{cout}_{hw}px"] = row
            continue
        for name, fn in (("conv_vmap", loss_conv),
                         ("im2col_matmul", loss_matmul)):
            g = jax.jit(jax.grad(fn))
            dtms = timeit(g, w) * 1e3
            row[f"{name}_fwdbwd_ms"] = round(dtms, 3)
        row["matmul_speedup_x"] = round(
            row["conv_vmap_fwdbwd_ms"] / row["im2col_matmul_fwdbwd_ms"],
            2)
        section[f"stage_{cin}x{cout}_{hw}px"] = row
        print(f"conv_lowering {cin}->{cout} @{hw}px: conv "
              f"{row['conv_vmap_fwdbwd_ms']:.2f} ms vs matmul "
              f"{row['im2col_matmul_fwdbwd_ms']:.2f} ms "
              f"(x{row['matmul_speedup_x']}, rel err "
              f"{row['agree_rel_err']})", file=sys.stderr)
    return section


def main():
    model = build_model()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(K_CLIENTS, BATCH, 32, 32, 3),
                    jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, (K_CLIENTS, BATCH)))
    params = model.init(jax.random.key(0))
    kparams = jax.vmap(lambda _: params)(jnp.arange(K_CLIENTS))

    def loss_fn(p, bx, by):
        logits = model.apply(p, bx)
        onehot = jax.nn.one_hot(by, logits.shape[-1])
        return -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits) * onehot, axis=-1))

    grad_step = jax.grad(loss_fn)

    @jax.jit
    def shared(p, bx, by):
        return grad_step(p, bx.reshape(-1, 32, 32, 3), by.reshape(-1))

    @jax.jit
    def vmapped(kp, bx, by):
        return jax.vmap(grad_step)(kp, bx, by)

    @jax.jit
    def scanned(kp, bx, by):
        def body(_, args):
            return None, grad_step(*args)
        return jax.lax.scan(body, None, (kp, bx, by))[1]

    devs = jax.devices()
    print(f"devices: {devs}", file=sys.stderr)
    out = {"platform": devs[0].device_kind,
           "config": {"clients": K_CLIENTS, "batch": BATCH,
                      "model": "resnet20", "dtype": "bfloat16"},
           "ms_per_step": {}}
    for name, fn, p in (("shared", shared, params),
                        ("vmapped", vmapped, kparams),
                        ("scanned", scanned, kparams)):
        dt = timeit(fn, p, x, y)
        out["ms_per_step"][name] = round(dt * 1e3, 2)
        print(f"{name:8s}: {dt*1e3:8.2f} ms for {K_CLIENTS}x{BATCH} "
              "images fwd+bwd", file=sys.stderr)
    out["vmap_penalty_x"] = round(
        out["ms_per_step"]["vmapped"] / out["ms_per_step"]["shared"], 2)
    out["conv_lowering"] = conv_lowering_ab()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "VMAP_PENALTY.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), file=sys.stderr)


if __name__ == "__main__":
    from fedtorch_tpu.utils import require_tpu

    require_tpu("vmap_penalty_bench.py")
    enable_compile_cache()
    main()
