"""Operations and bytes of the Olmo-Hybrid configuration from its
shapes, read from the configuration's file.

``train_flops_per_image()`` is the model's training FLOPs for one
sample of the round, which is a SEQUENCE here: every matrix product of
the forward pass once (two FLOPs a multiply-accumulate), the causal
softmax attention and the delta rule's recurrence, times three for the
forward pass and the two products of the backward pass. Recomputation
(the layers run under ``jax.checkpoint``) is not counted, nor are
norms, gates, the depthwise convolution and the embedding's lookup.

The mixers' own counts are **of the mathematics, not of the chunking
or tiling** (operands read once, results written once), so that a
later kernel is read against the same work whatever implements it:

* the gated delta rule, per token and head, is the recurrence: decay
  the state (``d_k d_v`` multiplies), read it for the key, write the
  rank-one update, read it for the query (three products of
  ``d_k d_v`` multiply-accumulates): ``7 d_k d_v`` FLOPs forward, and
  twice that backward;
* causal softmax attention is ``Q K^T`` and ``P V`` over the
  ``T (T + 1) / 2`` pairs a causal mask keeps, and twice that backward.

Bytes: q, k, v in and o out in the compute type (2 bytes), decay and
step in float32; the backward pass reads them and the output's
cotangent (attention: the output too) and writes the inputs'
cotangents.
"""
from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "olmo_hybrid_7b_l4.json")
ACT_BYTES = 2       # bfloat16 operands


def spec(path: str = _FILE) -> dict:
    with open(path) as f:
        doc = json.load(f)
    doc["layer_types"] = doc["layer_types"][:doc["num_hidden_layers"]]
    doc["seq_len"] = doc["datagen"]["seq_len"]
    return doc


def layer_counts(s: dict) -> dict:
    kinds = s["layer_types"]
    return {"linear": kinds.count("linear_attention"),
            "full": kinds.count("full_attention")}


def forward_matmul_macs_per_token(s: dict) -> int:
    """Multiply-accumulates of the weight matrices a token passes."""
    d, f = s["hidden_size"], s["intermediate_size"]
    h = s["linear_num_key_heads"]
    qk, vv = h * s["linear_key_head_dim"], h * s["linear_value_head_dim"]
    mlp = 3 * d * f
    linear = d * (2 * qk + 2 * vv + 2 * h) + vv * d + mlp
    full = 4 * d * d + mlp
    n = layer_counts(s)
    return n["linear"] * linear + n["full"] * full + d * s["vocab_size"]


def delta_rule_flops(tokens: int, s: dict) -> int:
    """One call (one layer, one sequence), forward and backward."""
    per = 7 * s["linear_key_head_dim"] * s["linear_value_head_dim"]
    return 3 * tokens * s["linear_num_key_heads"] * per


def delta_rule_bytes(tokens: int, s: dict) -> int:
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    ins = (2 * dk + dv) * ACT_BYTES + 2 * 4        # q, k, v; g, beta
    out = dv * ACT_BYTES
    forward = ins + out
    backward = ins + out + ins                     # + do in, grads out
    return tokens * s["linear_num_key_heads"] * (forward + backward)


def attention_flops(tokens: int, s: dict) -> int:
    """One call of causal softmax attention, forward and backward."""
    pairs = tokens * (tokens + 1) // 2
    return 3 * 2 * 2 * pairs * s["hidden_size"]    # QK^T and PV


def attention_bytes(tokens: int, s: dict) -> int:
    row = tokens * s["hidden_size"] * ACT_BYTES
    return 4 * row + 8 * row      # q k v in, o out; q k v o do in, 3 out


def train_flops_per_image(s: dict = None) -> int:
    s = s or spec()
    t = s["seq_len"]
    n = layer_counts(s)
    return (3 * 2 * forward_matmul_macs_per_token(s) * t
            + n["full"] * attention_flops(t, s)
            + n["linear"] * delta_rule_flops(t, s))
