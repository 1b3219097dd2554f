"""Operations and bytes of the Keye-VL-2.0 configuration (one chip's
share: ``benchmark/configs/keye_vl2_30b_a3b_l4.json``) from its shapes.

The first model here whose operations depend on the data (the
token-expert pairs routed to the experts held) and on a selection
(``min(t + 1, topk)`` keys a query). Both are counted **of the
mathematics**: the selected pairs, not the pairs a masked dense product
computes, and the EXPECTED pairs on the experts held under a uniform
router, ``T x num_experts_per_tok x held / routed`` a sequence and
layer (the readers of a traced run put the round's own count,
``lm_moe_pairs_local``, in its place). Recomputation (layers and query
chunks run under ``jax.checkpoint``) is not counted, nor are norms, the
rotary turn, the softmax, the threshold's search, sort and gathers, and
the embedding's lookup.

``train_flops_per_image()`` is the model's training FLOPs for one
sample of the round, which is a SEQUENCE here: every matrix product of
the layers (two FLOPs a multiply-accumulate) and the head, times three
for the forward pass and the two products of the backward pass; the
indexer's three projections times two, because their input is under
``stop_gradient`` and the backward pass has the weights' product alone.

The per-scope counts are one *layer call* each, forward and backward,
operands read once and results written once:

* ``indexer_*`` (scope ``lm.indexer``): the three projections and the
  scores ``qI . kI`` over the ``T (T + 1) / 2`` causal pairs of 16
  heads of 64; bytes: the layer's input and the three float32 matrices
  in (the matrices' gradient out), qI, kI and w written and read, and
  the selection's mask (a byte a causal pair) written once and read
  once;
* ``selected_attention_*`` (scope ``lm.attention`` in this cell):
  ``q k^T`` and ``p v`` over the selected pairs of 32 heads of 128;
  q, k, v in and o out in the compute type (k and v of 4 heads), the
  backward pass reads them, o and its cotangent and writes three
  cotangents;
* ``experts_*`` (scopes ``lm.router`` + ``lm.experts``): the router's
  product over all tokens and the three grouped products over
  ``pairs`` buffer rows; bytes: the router's and the 16 experts'
  float32 matrices read forward and backward and their gradient
  written, the buffer's rows (input, hidden, output) written and read.
"""
from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "keye_vl2_30b_a3b_l4.json")
ACT_BYTES = 2       # bfloat16 operands
F32 = 4             # parameters, their gradients, the residual stream


def spec(path: str = _FILE) -> dict:
    with open(path) as f:
        doc = json.load(f)
    doc["seq_len"] = doc["datagen"]["seq_len"]
    doc["routed_experts"] = doc.get("published", {}).get(
        "num_experts", doc["num_experts"])
    return doc


def layer_counts(s: dict) -> dict:
    """Layer calls a sequence, by kind."""
    return {"full": s["num_hidden_layers"]}


def causal_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def selected_pairs(tokens: int, s: dict) -> int:
    """``sum_t min(t + 1, topk)``."""
    full = min(tokens, s["sa_config"]["topk"])
    return causal_pairs(full) + (tokens - full) * s["sa_config"]["topk"]


def expected_pairs(tokens: int, s: dict) -> float:
    """Token-expert pairs a sequence sends to the experts held, a
    layer, under a uniform router."""
    return tokens * s["num_experts_per_tok"] * s["num_experts"] \
        / s["routed_experts"]


def attention_width(s: dict) -> int:
    return s["num_attention_heads"] * s["head_dim"]


def kv_width(s: dict) -> int:
    return s["num_key_value_heads"] * s["head_dim"]


def indexer_params(s: dict) -> int:
    sa = s["sa_config"]
    return s["hidden_size"] * (sa["indexer_num_heads"]
                               * (sa["indexer_head_dim"] + 1)
                               + sa["indexer_head_dim"])


def expert_params(s: dict) -> int:
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def projection_flops(tokens: int, s: dict) -> int:
    """W_q, W_k, W_v, W_o of a layer call, forward and backward."""
    d = s["hidden_size"]
    return 3 * 2 * tokens * d * (2 * attention_width(s) + 2 * kv_width(s))


def indexer_flops(tokens: int, s: dict) -> int:
    sa = s["sa_config"]
    scores = 2 * causal_pairs(tokens) * sa["indexer_num_heads"] \
        * sa["indexer_head_dim"]
    return 2 * 2 * tokens * indexer_params(s) + 3 * scores


def indexer_bytes(tokens: int, s: dict) -> int:
    sa = s["sa_config"]
    out = tokens * (sa["indexer_num_heads"] * (sa["indexer_head_dim"] + 1)
                    + sa["indexer_head_dim"]) * ACT_BYTES
    return 2 * tokens * s["hidden_size"] * ACT_BYTES \
        + 2 * indexer_params(s) * F32 + 4 * out + 2 * causal_pairs(tokens)


def selected_attention_flops(tokens: int, s: dict) -> int:
    return 3 * 2 * 2 * selected_pairs(tokens, s) * attention_width(s)


def selected_attention_bytes(tokens: int, s: dict) -> int:
    rows = tokens * (attention_width(s) + kv_width(s)) * ACT_BYTES
    return 2 * rows + 4 * rows      # q k v in, o out; q k v o do in, 3 out


def experts_flops(tokens: int, s: dict, pairs: float = None) -> float:
    pairs = expected_pairs(tokens, s) if pairs is None else pairs
    router = 2 * tokens * s["hidden_size"] * s["routed_experts"]
    return 3 * (router + 2 * pairs * expert_params(s))


def experts_bytes(tokens: int, s: dict, pairs: float = None) -> float:
    pairs = expected_pairs(tokens, s) if pairs is None else pairs
    weights = s["hidden_size"] * s["routed_experts"] \
        + s["num_experts"] * expert_params(s)
    row = (2 * s["hidden_size"] + 2 * s["moe_intermediate_size"]) \
        * ACT_BYTES + s["hidden_size"] * F32
    return 3 * weights * F32 + 2 * 2 * pairs * row


def head_flops(tokens: int, s: dict) -> int:
    return 3 * 2 * tokens * s["hidden_size"] * s["vocab_size"]


def layer_flops(tokens: int, s: dict) -> float:
    """One layer call, forward and backward."""
    return projection_flops(tokens, s) + indexer_flops(tokens, s) \
        + selected_attention_flops(tokens, s) + experts_flops(tokens, s)


def train_flops_per_image(s: dict = None) -> float:
    s = s or spec()
    t = s["seq_len"]
    return layer_counts(s)["full"] * layer_flops(t, s) + head_flops(t, s)
