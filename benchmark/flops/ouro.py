"""Operations and bytes of the Ouro configuration from its shapes, read
from the configuration's file.

The model is a loop: its ``n`` layers run ``R = total_ut_steps`` times
a sequence, and a head follows every pass. XLA's cost analysis counts a
loop's body once, so everything here is counted from shapes, ``R``
passes of it.

``train_flops_per_image()`` is the model's training FLOPs for one
sample of the round, which is a SEQUENCE here: ``R`` passes of every
matrix product of the layers (two FLOPs a multiply-accumulate) and of
the causal softmax attention, and ``R`` heads, times three for the
forward pass and the two products of the backward pass. Recomputation
(layers and exits run under ``jax.checkpoint``) is not counted, nor are
norms, the rotary turn, the gate and the embedding's lookup.

The per-scope counts are **of the mathematics, not of the tiling**
(operands read once, results written once), one *call* each, forward
and backward, so that a later kernel is read against the same work:

* ``attention_*``: one layer call's causal softmax core, ``Q K^T`` and
  ``P V`` over the ``T (T + 1) / 2`` pairs a causal mask keeps; q, k, v
  in and o out in the compute type, the backward pass reads them, the
  output and its cotangent and writes three cotangents;
* ``loop_stack_*``: one sequence's whole loop, ``R x n`` layer calls
  (projections, attention core, SwiGLU): a layer call reads its
  float32 weights once forward and once backward and writes their
  gradient once, and reads and writes the float32 residual stream at
  the layer's boundary (in and out forward; saved input and cotangent
  in, cotangent out backward);
* ``exit_*``: one sequence's ``R`` exits: the head's product (logits
  are an intermediate of the loss and go nowhere), its float32 weights
  read forward and backward and their gradient written, the pass's
  output read and its cotangent written; the gate's 2048-term sums are
  left out.
"""
from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "ouro_2_6b_l8.json")
ACT_BYTES = 2       # bfloat16 operands
F32 = 4             # parameters, their gradients, the residual stream


def spec(path: str = _FILE) -> dict:
    with open(path) as f:
        doc = json.load(f)
    doc["layer_types"] = doc["layer_types"][:doc["num_hidden_layers"]]
    doc["seq_len"] = doc["datagen"]["seq_len"]
    return doc


def layer_counts(s: dict) -> dict:
    """Layer calls a sequence, by kind."""
    return {"full": s["total_ut_steps"] * len(s["layer_types"])}


def layer_matmul_macs_per_token(s: dict) -> int:
    d, f = s["hidden_size"], s["intermediate_size"]
    return 4 * d * d + 3 * d * f


def attention_flops(tokens: int, s: dict) -> int:
    """One call of causal softmax attention, forward and backward."""
    pairs = tokens * (tokens + 1) // 2
    return 3 * 2 * 2 * pairs * s["hidden_size"]    # QK^T and PV


def attention_bytes(tokens: int, s: dict) -> int:
    row = tokens * s["hidden_size"] * ACT_BYTES
    return 4 * row + 8 * row      # q k v in, o out; q k v o do in, 3 out


def loop_stack_flops(tokens: int, s: dict) -> int:
    """One sequence's ``R x n`` layer calls, forward and backward."""
    call = 3 * 2 * layer_matmul_macs_per_token(s) * tokens \
        + attention_flops(tokens, s)
    return layer_counts(s)["full"] * call


def loop_stack_bytes(tokens: int, s: dict) -> int:
    weights = layer_matmul_macs_per_token(s) * F32
    stream = tokens * s["hidden_size"] * F32
    call = 3 * weights + 5 * stream
    return layer_counts(s)["full"] * call


def exit_flops(tokens: int, s: dict) -> int:
    """One sequence's ``R`` heads, forward and backward."""
    return s["total_ut_steps"] * 3 * 2 * s["hidden_size"] \
        * s["vocab_size"] * tokens


def exit_bytes(tokens: int, s: dict) -> int:
    head = s["hidden_size"] * s["vocab_size"] * F32
    stream = tokens * s["hidden_size"] * F32
    return s["total_ut_steps"] * (3 * head + 2 * stream)


def train_flops_per_image(s: dict = None) -> int:
    s = s or spec()
    t = s["seq_len"]
    return loop_stack_flops(t, s) + exit_flops(t, s)
