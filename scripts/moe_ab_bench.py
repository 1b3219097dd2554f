"""Sparse-MoE dispatch A/B on the real TPU.

The framework's Switch-MoE transformer defaults to EXACT dense dispatch
(every token visits every expert — E x the MLP FLOPs, bit-stable) with
sparse capacity dispatch (`capacity_factor > 0`, O(capacity) FLOPs,
over-capacity tokens dropped to the residual) as opt-in. The perf-
relevant mode at scale is sparse, but no measurement on any hardware has
shown the capacity-factor cost/quality trade actually realized.

This script times full training steps (loss incl. Switch aux loss +
backward + SGD, jitted, bf16) of an E=16 Switch transformer:

  dense        capacity_factor=0   (the exactness oracle)
  cf1.0 / cf1.25 / cf2.0           (sparse, growing capacity headroom)

reporting per-config step time, measured per-layer drop fraction, and a
short same-seed loss trajectory (sparse must track dense closely while
costing a fraction of its step time — that is the case for flipping the
recommended large-E training config to sparse).

Writes MOE_AB.json; prints one JSON line. TPU only: main() exits
non-zero without a chip and when any case failed. To smoke-test the
plumbing off-chip, import ``run_case`` directly under
``JAX_PLATFORMS=cpu`` (run_case("dense", 0.0) with the MOE_AB_* size
overrides).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedtorch_tpu.telemetry.costs import FLOPS_XLA, lowered_cost


def log(*a):
    print(*a, file=sys.stderr, flush=True)


B = int(os.environ.get("MOE_AB_BATCH", "8"))
T = int(os.environ.get("MOE_AB_SEQ", "256"))
E = int(os.environ.get("MOE_AB_EXPERTS", "16"))
D_MODEL, HEADS, LAYERS, VOCAB = 256, 8, 4, 256
ITERS = int(os.environ.get("MOE_AB_ITERS", "10"))
LOSS_STEPS = int(os.environ.get("MOE_AB_LOSS_STEPS", "30"))
AUX_WEIGHT = 0.01


def run_case(name, capacity_factor):
    import jax
    import jax.numpy as jnp
    import optax

    from fedtorch_tpu.models.transformer import (
        TransformerLM, drop_fractions,
    )

    model = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL,
                          num_heads=HEADS, num_layers=LAYERS,
                          max_len=T, dtype="bfloat16", num_experts=E,
                          capacity_factor=capacity_factor)
    toks = jax.random.randint(jax.random.key(1), (B, T), 0, VOCAB)
    tgts = jnp.roll(toks, -1, axis=1)
    # same init for every case: the dispatch mode is the only variable
    params = TransformerLM(
        vocab_size=VOCAB, d_model=D_MODEL, num_heads=HEADS,
        num_layers=LAYERS, max_len=T, dtype="bfloat16", num_experts=E,
    ).init(jax.random.key(0), toks)["params"]
    opt = optax.sgd(0.05)

    @jax.jit
    def train_step(params, state):
        def loss_fn(p):
            logits, mods = model.apply(
                {"params": p}, toks, mutable=["aux_loss"])
            logp = jax.nn.log_softmax(logits)
            ce = -jnp.mean(jnp.take_along_axis(
                logp, tgts[..., None], axis=-1))
            aux = sum(jnp.sum(v) for v in
                      jax.tree.leaves(mods.get("aux_loss", {})))
            return ce + AUX_WEIGHT * aux, ce

        (loss, ce), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        upd, state = opt.update(g, state)
        return optax.apply_updates(params, upd), state, ce

    state = opt.init(params)
    t0 = time.time()
    params, state, ce = train_step(params, state)
    jax.block_until_ready(ce)
    compile_s = time.time() - t0

    # executed FLOPs per step from XLA cost analysis (the shared
    # telemetry.costs extractor): the dense-vs-sparse FLOPs ratio is
    # hardware-independent evidence even when the wall-clock is
    # measured off-chip (VERDICT r4 #6). Persistent compile cache
    # makes the AOT re-compile cheap.
    try:
        step_flops = lowered_cost(
            train_step.lower(params, state)).get("flops")
    except Exception:
        step_flops = None

    # keep device arrays (no host sync inside the timed loop) so the
    # loss trajectory starts at step 1, not after the warmup steps
    loss_dev = [ce]
    t0 = time.time()
    for _ in range(ITERS):
        params, state, ce = train_step(params, state)
        loss_dev.append(ce)
    jax.block_until_ready(ce)
    step_ms = (time.time() - t0) / ITERS * 1e3

    for _ in range(LOSS_STEPS - ITERS - 1):
        params, state, ce = train_step(params, state)
        loss_dev.append(ce)
    losses = [float(x) for x in loss_dev]

    drops = drop_fractions(model, params, toks)
    drop = {k: round(float(v), 4) for k, v in drops.items()}
    row = {"capacity_factor": capacity_factor,
           "step_ms": round(step_ms, 2),
           "flops_per_step": step_flops,
           "flops_source": FLOPS_XLA if step_flops else None,
           "compile_s": round(compile_s, 1),
           "final_ce": round(losses[-1], 4),
           "loss_first5": [round(x, 4) for x in losses[:5]],
           "drop_fraction_per_layer": drop,
           "max_drop_fraction": round(max(drop.values()), 4)
           if drop else 0.0}
    log(f"{name:7s}: {step_ms:8.2f} ms/step  ce={losses[-1]:.4f}  "
        f"max_drop={row['max_drop_fraction']:.3f}  "
        f"(compile {compile_s:.0f}s)")
    return row


def main():
    from fedtorch_tpu.utils import enable_compile_cache, require_tpu
    device = require_tpu("moe_ab_bench.py")
    enable_compile_cache()
    log(f"device: {device}")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = {"device": device,
               "config": {"batch": B, "seq": T, "experts": E,
                          "d_model": D_MODEL, "layers": LAYERS,
                          "dtype": "bfloat16",
                          "loss_steps": LOSS_STEPS},
               "cases": {}}
    for name, cf in (("dense", 0.0), ("cf1.0", 1.0),
                     ("cf1.25", 1.25), ("cf2.0", 2.0)):
        try:
            results["cases"][name] = run_case(name, cf)
        except Exception as e:
            results["cases"][name] = {"error": str(e)[:300]}
            log(f"{name}: FAIL {str(e)[:160]}")
        with open(os.path.join(repo, "MOE_AB.json"), "w") as f:
            json.dump(results, f, indent=1)

    dense = results["cases"].get("dense", {})
    sparse = results["cases"].get("cf1.25", {})
    speedup = None
    if "step_ms" in dense and "step_ms" in sparse:
        speedup = round(dense["step_ms"] / sparse["step_ms"], 2)
    ok = all("error" not in c for c in results["cases"].values())
    print(json.dumps({"moe_ab_ok": ok,
                      "sparse_cf1.25_speedup_vs_dense": speedup,
                      "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
