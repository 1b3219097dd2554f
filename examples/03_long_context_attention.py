"""Long-context inference, sequence-parallel over a device mesh.

The transformer LM's attention can run under either EXACT
sequence-parallel strategy (parallel/sequence.py):

* ring — each device holds one block of queries; K/V blocks rotate
  around the ring via ``ppermute`` (per-device score memory O(T^2/n^2));
* ulysses — two all-to-alls re-shard sequence->heads and back; plain
  attention runs on full sequence for the local head slice.

This example runs a 2048-token context over an 8-way mesh under BOTH
strategies and checks each against single-device dense attention.

Run (no TPU needed):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/03_long_context_attention.py
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from fedtorch_tpu.models.transformer import TransformerLM, \
    long_context_apply

SEQ_LEN, VOCAB = 2048, 128

devices = jax.devices()
mesh = Mesh(np.asarray(devices), ("sp",))
print(f"sequence axis sharded over {len(devices)} devices")

# 8 heads: ulysses shards heads over the 8-way mesh (ring has no
# head-count requirement)
model = TransformerLM(vocab_size=VOCAB, num_layers=2, num_heads=8,
                      d_model=64, max_len=SEQ_LEN)
tokens = jax.random.randint(jax.random.key(1), (1, SEQ_LEN), 0, VOCAB)
params = model.init(jax.random.key(0), tokens)["params"]

# single-device baseline: ordinary causal attention
logits_full = model.apply({"params": params}, tokens)

for strategy in ("ring", "ulysses"):
    logits = long_context_apply(model, params, tokens, mesh,
                                strategy=strategy)
    err = float(jnp.max(jnp.abs(logits - logits_full)))
    print(f"{strategy:8s}: max |sharded - dense| over "
          f"[1, {SEQ_LEN}, {VOCAB}] logits = {err:.2e}")
    assert err < 1e-3, f"{strategy} diverged from the exact baseline"
print("ok: both sequence-parallel strategies exact at "
      f"{SEQ_LEN} tokens x {len(devices)} shards")
