"""Operations and bytes of the kanana-2 configuration (one chip's share:
``benchmark/configs/kanana2_30b_a3b_l5.json``) from its shapes.

Latent attention: a layer's own four products (``W_q``, ``W_a``,
``W_b``, ``W_o``: ``latent_*``, scope ``lm.latent``) beside causal
softmax attention whose query/key heads are ``qk_nope_head_dim +
qk_rope_head_dim`` wide and whose value heads ``v_head_dim``
(``attention_*``, scope ``lm.attention``): a causal pair of a head costs
``2 x 192`` FLOPs of ``q k^T`` and ``2 x 128`` of ``p v``, counted as
they are, with no head padded to the other's size. One leading dense
layer (scope ``lm.mlp``), then expert layers: the router over all
``published.n_routed_experts`` outputs, the grouped products over the
token-expert pairs routed to the experts HELD (``experts_*``, scopes
``lm.router`` + ``lm.experts``; the EXPECTED pairs under a uniform
router, ``T x num_experts_per_tok x held / routed`` a sequence and
layer, unless a reader puts the round's own count,
``lm_moe_pairs_local``, in their place), and the shared expert every
chip computes whole (``shared_*``, scope ``lm.shared``). All **of the
mathematics**: recomputation (layers run under ``jax.checkpoint``) is
not counted, nor are norms, the rotary turn, the softmax, the sort and
gathers of the dispatch, and the embedding's lookup.

``train_flops_per_image()`` is the model's training FLOPs for one
sample of the round, which is a SEQUENCE here: every matrix product of
the layers (two FLOPs a multiply-accumulate) and the head, times three
for the forward pass and the two products of the backward pass.

The per-scope counts are one *layer call* each, forward and backward,
operands read once and results written once (bfloat16 activations,
float32 weights and their gradients).
"""
from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "kanana2_30b_a3b_l5.json")
ACT_BYTES = 2       # bfloat16 operands
F32 = 4             # parameters, their gradients, the residual stream


def spec(path: str = _FILE) -> dict:
    with open(path) as f:
        doc = json.load(f)
    doc["seq_len"] = doc["datagen"]["seq_len"]
    doc["routed_experts"] = doc.get("published", {}).get(
        "n_routed_experts", doc["n_routed_experts"])
    return doc


def layer_counts(s: dict) -> dict:
    """Layer calls a sequence, by kind: every layer's attention is
    latent; the leading ones' feed-forward dense, the rest expert
    layers."""
    n, dense = s["num_hidden_layers"], s["first_k_dense_replace"]
    return {"latent": n, "dense": dense, "expert": n - dense}


def causal_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def expected_pairs(tokens: int, s: dict) -> float:
    """Token-expert pairs a sequence sends to the experts held, a
    layer, under a uniform router."""
    return tokens * s["num_experts_per_tok"] * s["n_routed_experts"] \
        / s["routed_experts"]


def qk_width(s: dict) -> int:
    return s["num_attention_heads"] * (s["qk_nope_head_dim"]
                                       + s["qk_rope_head_dim"])


def v_width(s: dict) -> int:
    return s["num_attention_heads"] * s["v_head_dim"]


def latent_params(s: dict) -> int:
    """W_q, W_a, W_b, W_o of a layer (without the latent's norm)."""
    d, rank, h = s["hidden_size"], s["kv_lora_rank"], \
        s["num_attention_heads"]
    return d * qk_width(s) + d * (rank + s["qk_rope_head_dim"]) \
        + rank * h * (s["qk_nope_head_dim"] + s["v_head_dim"]) \
        + v_width(s) * d


def expert_params(s: dict) -> int:
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def shared_params(s: dict) -> int:
    return s["n_shared_experts"] * expert_params(s)


def dense_params(s: dict) -> int:
    return 3 * s["hidden_size"] * s["intermediate_size"]


def latent_flops(tokens: int, s: dict) -> int:
    """The sublayer's four products of a layer call, forward and
    backward."""
    return 3 * 2 * tokens * latent_params(s)


def latent_bytes(tokens: int, s: dict) -> int:
    """The four float32 matrices read forward and backward and their
    gradient written; the layer's input, q, the latent with its rotary
    key, k_n and v, the attention's output and the sublayer's result
    written and read once each way."""
    row = (2 * s["hidden_size"] + qk_width(s) + s["kv_lora_rank"]
           + s["qk_rope_head_dim"] + s["num_attention_heads"]
           * (s["qk_nope_head_dim"] + s["v_head_dim"]) + v_width(s)) \
        * ACT_BYTES
    return 3 * latent_params(s) * F32 + 2 * 2 * tokens * row


def attention_flops(tokens: int, s: dict) -> int:
    """One call of causal softmax attention, forward and backward:
    ``q k^T`` over heads of 192 and ``p v`` over heads of 128."""
    return 3 * 2 * causal_pairs(tokens) * (qk_width(s) + v_width(s))


def attention_bytes(tokens: int, s: dict) -> int:
    """q and k (192 a head) and v (128) in, o (128) out; the backward
    pass reads them, o and its cotangent and writes three cotangents."""
    qk, v = tokens * qk_width(s) * ACT_BYTES, tokens * v_width(s) * ACT_BYTES
    forward = 2 * qk + 2 * v
    return forward + (forward + v) + (2 * qk + v)


def experts_flops(tokens: int, s: dict, pairs: float = None) -> float:
    pairs = expected_pairs(tokens, s) if pairs is None else pairs
    router = 2 * tokens * s["hidden_size"] * s["routed_experts"]
    return 3 * (router + 2 * pairs * expert_params(s))


def experts_bytes(tokens: int, s: dict, pairs: float = None) -> float:
    pairs = expected_pairs(tokens, s) if pairs is None else pairs
    weights = s["hidden_size"] * s["routed_experts"] \
        + s["n_routed_experts"] * expert_params(s)
    row = (2 * s["hidden_size"] + 2 * s["moe_intermediate_size"]) \
        * ACT_BYTES + s["hidden_size"] * F32
    return 3 * weights * F32 + 2 * 2 * pairs * row


def shared_flops(tokens: int, s: dict) -> int:
    return 3 * 2 * tokens * shared_params(s)


def shared_bytes(tokens: int, s: dict) -> int:
    width = s["n_shared_experts"] * s["moe_intermediate_size"]
    row = (2 * s["hidden_size"] + 2 * width) * ACT_BYTES
    return 3 * shared_params(s) * F32 + 2 * 2 * tokens * row


def dense_flops(tokens: int, s: dict) -> int:
    return 3 * 2 * tokens * dense_params(s)


def head_flops(tokens: int, s: dict) -> int:
    return 3 * 2 * tokens * s["hidden_size"] * s["vocab_size"]


def train_flops_per_image(s: dict = None) -> float:
    s = s or spec()
    t, n = s["seq_len"], layer_counts(s)
    return n["latent"] * (latent_flops(t, s) + attention_flops(t, s)) \
        + n["dense"] * dense_flops(t, s) \
        + n["expert"] * (experts_flops(t, s) + shared_flops(t, s)) \
        + head_flops(t, s)
