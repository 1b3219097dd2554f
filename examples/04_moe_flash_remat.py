"""Scaling levers for the transformer LM: sparse MoE, flash attention,
rematerialization.

Three independent knobs on the same model, composable:

* ``num_experts`` + ``capacity_factor`` — Switch-style top-1 MoE blocks
  with capacity-bounded sparse dispatch: cf× the dense-MLP FLOPs no
  matter how many experts (experts shard over an ``ep`` mesh axis —
  parallel/expert.py); ``moe_aux_weight`` adds the load-balance loss and
  ``routing_fractions`` watches for gate collapse.
* ``attention='flash'`` — fused online-softmax attention (a Pallas
  kernel on TPU, dense fallback elsewhere): O(block²) score memory
  instead of O(T²).
* ``remat=True`` — per-block ``jax.checkpoint``: activation memory
  scales with one block instead of depth, ~1.33× FLOPs.

Run (no TPU needed):
    JAX_PLATFORMS=cpu python examples/04_moe_flash_remat.py
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from fedtorch_tpu.models.transformer import TransformerLM, \
    routing_fractions

VOCAB, SEQ = 128, 256
tokens = jax.random.randint(jax.random.key(1), (2, SEQ), 0, VOCAB)

# the plain dense model is the numerical baseline
base_kw = dict(vocab_size=VOCAB, d_model=64, num_heads=4, num_layers=2,
               max_len=SEQ)
dense = TransformerLM(**base_kw)
params = dense.init(jax.random.key(0), tokens)["params"]
ref = dense.apply({"params": params}, tokens)

# 1) flash attention: a backend swap — same params, same logits
flash = TransformerLM(**base_kw, attention="flash")
err = float(jnp.max(jnp.abs(flash.apply({"params": params}, tokens)
                            - ref)))
print(f"flash attention: max |flash - dense| = {err:.2e}")
assert err < 1e-4

# 2) remat: same params, same logits, same gradients — only the
#    backward's memory/FLOPs trade changes
remat = TransformerLM(**base_kw, remat=True)
err = float(jnp.max(jnp.abs(remat.apply({"params": params}, tokens)
                            - ref)))
print(f"remat:           max |remat - dense| = {err:.2e}")
assert err < 1e-6

# 3) sparse MoE: 8 experts at drop-free capacity (cf=8.0 here, so no
#    expert can overflow) — the sparse gather/scatter dispatch is EXACT
#    vs the dense (E x FLOPs) dispatch. Production capacities like the
#    cf=1.25 used in step 4 may drop tokens to the residual instead.
moe_kw = dict(base_kw, num_experts=8)
moe_dense = TransformerLM(**moe_kw)                      # E x FLOPs
moe_sparse = TransformerLM(**moe_kw, capacity_factor=8.0)  # no drops
moe_params = moe_dense.init(jax.random.key(0), tokens)["params"]
err = float(jnp.max(jnp.abs(
    moe_sparse.apply({"params": moe_params}, tokens)
    - moe_dense.apply({"params": moe_params}, tokens))))
print(f"sparse MoE (ample capacity): max |sparse - dense| = {err:.2e}")
assert err < 1e-4

fr = routing_fractions(moe_dense, moe_params, tokens)
for block, f in sorted(fr.items()):
    print(f"  {block} routing fractions: "
          f"{np.round(np.asarray(f), 3).tolist()}")

# 4) everything at once — the long-context training configuration
full = TransformerLM(**moe_kw, capacity_factor=1.25, attention="flash",
                     remat=True)
out = full.apply({"params": moe_params}, tokens)
grads = jax.grad(lambda p: jnp.sum(
    full.apply({"params": p}, tokens) ** 2))(moe_params)
finite = all(bool(jnp.all(jnp.isfinite(g)))
             for g in jax.tree.leaves(grads))
print(f"moe+flash+remat composed: logits {tuple(out.shape)}, "
      f"grads finite={finite}")
assert finite
print("ok: all three levers exact/finite, independently and composed")
