"""Transformer TRAINING throughput on the real TPU: dense vs flash
attention (vs flash+remat), at growing context length.

scripts/pallas_tpu_check.py times the attention FORWARD in isolation;
this script times full training steps (loss + backward + SGD update,
jitted, bf16) of a small causal LM, where the flash kernel's fused
forward and the chunked recompute-from-logsumexp VJP both participate —
the number a user choosing ``--attention flash`` actually experiences.
remat adds the activation-memory trade on top (expected: slightly
slower, much smaller activation footprint — enabling longer T).

Writes FLASH_TRAIN.json; prints one JSON line.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    from fedtorch_tpu.utils import enable_compile_cache, require_tpu
    device = require_tpu("flash_train_bench.py")
    enable_compile_cache()
    log(f"device: {device}")

    import jax
    import jax.numpy as jnp
    import optax

    from fedtorch_tpu.models.transformer import TransformerLM

    results = {"device": device, "cases": {}}
    B, D_MODEL, HEADS, LAYERS, VOCAB = 1, 256, 8, 4, 256

    def step_time(model, params, toks, tgts, iters=10):
        opt = optax.sgd(0.01)

        @jax.jit
        def train_step(params, state):
            def loss_fn(p):
                logits = model.apply({"params": p}, toks)
                logp = jax.nn.log_softmax(logits)
                return -jnp.mean(jnp.take_along_axis(
                    logp, tgts[..., None], axis=-1))

            loss, g = jax.value_and_grad(loss_fn)(params)
            upd, state = opt.update(g, state)
            return optax.apply_updates(params, upd), state, loss

        state = opt.init(params)
        t0 = time.time()
        params, state, loss = train_step(params, state)
        float(loss)  # the fetch waits for the device
        compile_s = time.time() - t0
        t0 = time.time()
        for _ in range(iters):
            params, state, loss = train_step(params, state)
        final_loss = float(loss)  # materialize BEFORE reading the clock
        return (time.time() - t0) / iters, compile_s, final_loss

    for T in (1024, 2048, 4096, 8192):
        toks = jax.random.randint(jax.random.key(1), (B, T), 0, VOCAB)
        tgts = jnp.roll(toks, -1, axis=1)
        row = {}
        base_params = None
        for name, kw in (("dense", {}),
                         ("flash", {"attention": "flash"}),
                         ("flash_remat", {"attention": "flash",
                                          "remat": True})):
            model = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL,
                                  num_heads=HEADS, num_layers=LAYERS,
                                  max_len=T, dtype="bfloat16", **kw)
            try:
                if base_params is None:
                    base_params = model.init(jax.random.key(0), toks)[
                        "params"]
                sec, compile_s, loss = step_time(model, base_params,
                                                 toks, tgts)
                row[name] = {"step_ms": round(sec * 1e3, 2),
                             "compile_s": round(compile_s, 1),
                             "loss": round(loss, 4)}
                log(f"T={T} {name}: {sec*1e3:.1f} ms/step "
                    f"(compile {compile_s:.1f}s, loss {loss:.3f})")
            except Exception as e:  # OOM at long T is itself a datum
                row[name] = {"error": str(e)[:200]}
                log(f"T={T} {name}: FAIL {str(e)[:120]}")
        if "step_ms" in row.get("dense", {}) \
                and "step_ms" in row.get("flash", {}):
            row["flash_speedup"] = round(
                row["dense"]["step_ms"] / row["flash"]["step_ms"], 2)
        results["cases"][f"T{T}"] = row

    with open("FLASH_TRAIN.json", "w") as f:
        json.dump(results, f, indent=1)
    speedups = [c.get("flash_speedup") for c in
                results["cases"].values() if c.get("flash_speedup")]
    print(json.dumps({
        "flash_train_ok": bool(speedups),
        "flash_speedup_range": [min(speedups), max(speedups)]
        if speedups else None,
        "device": device}))
    return 0 if speedups else 1


if __name__ == "__main__":
    sys.exit(main())
