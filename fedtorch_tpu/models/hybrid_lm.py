"""A causal language model whose shape is read from a file: the keys
of a public ``config.json`` (``hidden_size``, ``intermediate_size``,
``num_hidden_layers``, ``layer_types``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``vocab_size``,
``rms_norm_eps``; ``linear_*`` where a layer is a ``linear_attention``
one; ``num_experts`` ... and ``sa_config``, or ``kv_lora_rank`` ... and
``n_routed_experts`` ... as below), as the Olmo-Hybrid, the Ouro, the
Keye-VL-2.0 (Qwen3-MoE) and the kanana-2 (``deepseek_v3``) families
state them. ``layer_types`` names each layer's mixer (absent: every
layer a ``full_attention`` one, of a ``deepseek_v3`` file a
``latent_attention`` one):

* ``linear_attention`` — the gated delta rule (``ops/delta_rule.py``)
  behind a causal depthwise convolution, with an output gate;
* ``full_attention`` — causal softmax attention through
  ``ops/attention_dispatch.py`` (flash from 4096 tokens on), of
  ``num_attention_heads`` query heads of ``head_dim`` (default
  ``hidden_size / num_attention_heads``; the heads together need not be
  ``hidden_size`` wide) on ``num_key_value_heads`` key and value heads
  (default: as many; key head ``j`` serves query heads ``j H/KV ..``);
* ``latent_attention`` — DeepSeek-V2/V3's latent attention without the
  low-rank query path (``model_type`` ``deepseek_v3``; ``kv_lora_rank``,
  ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
  ``rope_interleave``; ``q_lora_rank`` null): ``q = W_q u`` in heads of
  ``nope + rope``; ``[c | k_r] = W_a u`` with the ``kv_lora_rank``-wide
  latent ``c`` under an RMSNorm of its own and ONE rotary key head
  ``k_r`` for all query heads; ``[k_n | v] = W_b c'`` in heads of
  ``nope + v_head_dim``; the rotary parts turned (interleaved pairs
  ``(2i, 2i + 1)`` where the file says so: :func:`pairs_apart` lays
  them out as rotate-half has them, which no dot product sees); causal
  softmax of ``q . [k_n | k_r] / sqrt(nope + rope)`` through the same
  dispatch, the value heads ``v_head_dim`` wide (the flash kernel takes
  a value head size of its own; nothing is padded); no QK-norm.

Every layer ends in a SwiGLU MLP or in an expert layer (chosen PER
LAYER in a ``deepseek_v3`` file), the head is untied, nothing has a
bias. Optional keys choose the rest:

* ``model_type`` — the family's block. ``ouro``: sandwich norms (an
  RMSNorm on each sublayer's input and one on its output, four scales
  a layer) and no QK-norm; ``KeyeVL2``: pre-norm (a sublayer reads the
  normed stream and adds to the bare one, two scales a layer:
  ``PRENORM_BLOCKS``) and an RMSNorm over each HEAD of q and k
  (``HEAD_NORM_BLOCKS``: two flags of the spec, ``prenorm`` and
  ``head_norm``); ``deepseek_v3``: pre-norm and no QK-norm; anything
  else: norms on each sublayer's OUTPUT alone and an RMSNorm over the
  whole projected q and k (the Olmo 2/3 placement);
* ``rope_theta`` (top level, or under ``rope_parameters``) — rotary
  position embedding on q and k of the full-attention layers
  (rotate-half, positions ``0..T-1``, angles in float32); absent or
  null: none. ``rope_scaling`` may hold the default embedding's
  ``mrope_section`` record (for text its three position streams are
  the same, so the sections fall together) and nothing else;
* ``total_ut_steps`` (default 1) — the stack of layers, closed by the
  final norm, runs that many times with the same parameters, each
  pass fed the one before. After every pass come the head and an exit
  gate (one ``hidden_size -> 1`` linear layer with a bias, shared by
  the passes), and the training loss is the exit-weighted objective of
  Zhu et al. 2025 ("Scaling Latent Reasoning via Looped Language
  Models", stage I) with the weight ``exit_entropy_beta`` (default
  0.05) on the exit distribution's entropy: :func:`exit_objective`.
  Evaluation reads the last pass. More than one pass is refused for a
  ``model_type`` whose looped form is not written here;
* ``num_experts`` with ``num_experts_per_tok``,
  ``moe_intermediate_size``, ``norm_topk_prob`` (pre-norm block only)
  — every layer's feed-forward is ONE CHIP'S SHARE of a sparse-expert
  layer (``ops/routed_experts.py``): the router scores
  ``published.num_experts`` experts (the file's own count where nothing
  was cut), a token goes to its ``num_experts_per_tok`` best, and the
  ``num_experts`` experts held here, ``first_expert_held`` (default 0)
  onwards, give their part of the result; dropless (the grouped
  products visit the pairs routed here, so a step's seconds follow the
  routing). A layer's experts are one stacked leaf a matrix.
  ``decoder_sparse_step`` other than 1 and a non-empty
  ``mlp_only_layers`` (dense layers among them) are refused;
* of a ``deepseek_v3`` file ``n_routed_experts`` (the experts HELD;
  ``published.n_routed_experts`` the router's width,
  ``first_expert_held`` the first one) with ``num_experts_per_tok``,
  ``moe_intermediate_size``, ``n_shared_experts``,
  ``first_k_dense_replace``, ``scoring_func``, ``topk_method``,
  ``norm_topk_prob``, ``routed_scaling_factor`` — the first
  ``first_k_dense_replace`` layers' feed-forward is a dense SwiGLU of
  ``intermediate_size``, every other layer's one chip's share of
  DeepSeek-V3's expert layer: ``s = sigmoid(W_r u)`` from a float32
  product, the ``num_experts_per_tok`` largest of ``s + b`` chosen
  (``topk_method`` ``noaux_tc``: ``b`` one float32 ``[routed]`` leaf a
  layer that no gradient of ``CE`` reaches), the
  gates ``routed_scaling_factor x s_e / (sum of the chosen s + 1e-20)``,
  the held experts' part of the sum, and beside it ONE shared expert, a
  SwiGLU of ``n_shared_experts x moe_intermediate_size``, which every
  chip computes whole. ``balance_loss_coef`` (``u``, default 0) scales
  the balance part of the training loss, ``L_B - stop_gradient(L_B)``
  with ``L_B = -u sum_layers sum_e b_e stop_gradient(sign(mean(c) -
  c_e))`` and ``c`` the step's token-expert pairs over ALL routed
  experts: value zero, gradient DeepSeek-V3's auxiliary-loss-free
  update of ``b`` (plain SGD at ``lr`` moves ``b_e`` by ``lr x u``
  towards the mean load) and nothing else. ``n_group`` / ``topk_group``
  other than 1, ``moe_layer_freq`` other than 1, a ``scoring_func``
  other than ``sigmoid`` or ``softmax``, another ``topk_method`` and a
  ``q_lora_rank`` that is not null are refused;
* ``sa_config`` (pre-norm block only: ``indexer_num_heads``,
  ``indexer_head_dim``, ``topk``, ``q_chunk_size``;
  ``indexer_num_kv_heads`` 1) — every full-attention layer reads a
  learned selection of ``topk`` keys a query
  (``ops/sparse_attention.py``, DeepSeek-V3.2-Exp's lightning indexer),
  in query chunks of ``q_chunk_size`` (which ``topk`` is a multiple
  of); rows no longer than ``topk`` run the same code and select every
  key. The training loss is then ``CE + L_I``, ``L_I`` the indexers' KL
  term summed over the layers, which trains the indexers' three
  matrices a layer and nothing else; evaluation reads ``CE``;
* ``embedding_init_std`` (default 0.02, as every other matrix) — the
  seeded embedding rows' standard deviation. At 0.02 the first
  attention layer's output (an average of values over the context,
  some 0.15 a component) is eight times the token's own row, so every
  token of a row carries nearly the same stream; a router then sends
  them all to the same ``num_experts_per_tok`` experts, and how many
  of those a chip holds is the draw of the seed. At 1
  (``torch.nn.Embedding``'s own default) a token's row leads its
  stream and the seeded router spreads the tokens as a trained one
  does.

Still refused by name: tied embeddings, attention biases, any other
``rope_scaling``, an odd ``head_dim`` (or rotary part of a latent head),
query heads that are no multiple of the key heads, grouped VALUE heads
in a linear-attention layer, a low-rank query path, grouped routing,
latent attention outside a ``deepseek_v3`` file.

``benchmark/reference/olmo_hybrid.py``, ``benchmark/reference/ouro.py``,
``benchmark/reference/keye_vl2.py`` and ``benchmark/reference/kanana2.py``
write the same equations out in plain float32 and list what the public
configs leave open.

Pure functions over a nested dict of float32 parameters; every layer
is a subtree of its own (``layer_<i>``: no stacked scan over layers, so
a single-pass model's gradient is consumed leaf by leaf; a looped
layer's gradient is a sum over the passes, which the scan over passes
accumulates). Products take bfloat16 operands where the launcher's
``compute_dtype`` says so and accumulate in float32; the residual
stream, norms, rotary angles, softmax, decays, the convolution, the
exit gate, router probabilities, scores and gates (and a sigmoid
router's own product, at the highest precision), the indexer's weighted
sum and the loss are float32.

With ``remat`` each layer runs under ``jax.checkpoint``, which keeps
the layer's input and, of the layer's matrix products (each result
carries a name: ``mixer.q`` ... ``mlp.down``; a latent layer's
``mixer.q`` / ``kv_a`` / ``kv_b`` / ``o``, a shared expert's
``shared.*``, a dense layer's among expert layers ``dense.*``:
:func:`layer_products`),
the float32 results that fit in the device's memory; the backward pass
runs the rest again: norms, activations, gates, the convolution, the
rotary turn, softmax and the delta rule. A product kept and a product
run again give the same bits, so the set changes the time and the
memory and never the gradients. :func:`kept_products` chooses the set
from shapes alone (the products with the longest inner dimension
first: a ``[T, K] x [K, N]`` result spares ``K / 2`` FLOPs a byte
kept) within :func:`residual_budget`, what the device has left when
the step is first traced; one decision a shape and process
(:func:`_kept_for`), all of them where the backend reports no memory
(the CPU). The heads of a looped model (:func:`exit_stats`) and the
delta rule's scan keep their own checkpoints, which keep nothing; a
selected layer also keeps its attention's output, whatever the budget
(``sparse_attention.KEPT[1]``; of the fused kernels' form the
log-sum-exp, the thresholds and the indexer's scores besides).

Scopes for the device trace: ``lm.delta_rule``, ``lm.attention``,
``lm.mlp``, ``lm.head``; in a looped model also ``lm.loop`` (the scan
over passes: what it holds outside ``lm.attention`` and ``lm.mlp`` are
the projections, norms and rotary embedding) and ``lm.exit`` (the
heads, per-exit losses, gate and mixture; ``lm.head`` inside it);
under ``sa_config`` ``lm.indexer`` (the indexer's three products, its
scores, the selection and the KL term) beside ``lm.attention`` (the
masked attention of the chunks and the heads' summed probabilities);
in an expert layer ``lm.router`` (the router's product, softmax and
top-k, the sort and the row blocks' fill) and ``lm.experts`` (the
grouped products over the blocks in use and the combine) in
``lm.mlp``'s place; in a latent-attention
layer ``lm.latent`` (all of the sublayer but its softmax attention: the
four products, the latent's norm, the rotary turn) beside
``lm.attention``; beside a biased router's ``lm.router`` (which also
holds the load's count and the balance part) ``lm.shared`` (the shared
expert), and ``lm.mlp`` for the leading dense layers.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from fedtorch_tpu.ops import routed_experts, sparse_attention
from fedtorch_tpu.ops.attention_dispatch import on_tpu, resolve_attention
from fedtorch_tpu.ops.delta_rule import chunk_gated_delta_rule

LAYER_KINDS = ("linear_attention", "full_attention", "latent_attention")
INIT_STD = 0.02
# ``model_type`` values whose block differs from the Olmo placement
SANDWICH_BLOCKS = ("ouro",)
# ``model_type`` values whose block is pre-norm (a sublayer reads the
# normed stream and adds to the bare one, two scales a layer) ...
PRENORM_BLOCKS = ("KeyeVL2", "deepseek_v3")
# ... and those whose full-attention layers have an RMSNorm over each
# head of q and k
HEAD_NORM_BLOCKS = ("KeyeVL2",)
# of a rematerialized step's memory, how many times the widest layer's
# product results and the head's logits are set aside for the one
# layer and head at work (:func:`residual_budget`)
WORKING_SETS = 5
# the products that close a sublayer: their float32 results feed a norm,
# so the forward pass writes them whether they are kept or not (an input
# product's result feeds an epilogue the product fuses): first among
# products of equal inner dimension (:func:`kept_products`)
SUBLAYER_OUTPUTS = ("mixer.o", "mlp.down", "dense.down")
# the file's keys: asked of every file, and of one with a
# ``linear_attention`` layer
PLAIN_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "layer_types", "num_attention_heads", "rms_norm_eps")
# ... of one with ``num_experts``, and of its ``sa_config``
EXPERT_KEYS = ("num_experts_per_tok", "moe_intermediate_size")
# ... of a ``deepseek_v3`` file: its latent attention and its experts
LATENT_KEYS = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
               "v_head_dim")
DEEPSEEK_EXPERT_KEYS = EXPERT_KEYS + (
    "n_routed_experts", "n_shared_experts", "first_k_dense_replace",
    "scoring_func", "topk_method", "routed_scaling_factor")
SELECTION_KEYS = ("indexer_num_heads", "indexer_head_dim", "topk",
                  "q_chunk_size")
# the keys a ``rope_scaling`` record may hold: the default rotary
# embedding with its multimodal sections, which fall together for text
MROPE_KEYS = {"mrope_section", "rope_type", "type"}
LINEAR_KEYS = ("linear_num_key_heads", "linear_num_value_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim")


class Experts(NamedTuple):
    """A sparse-expert feed-forward: the router's width, this chip's
    share of the experts (``first .. first + held - 1``), experts a
    token, an expert's width, and whether the chosen probabilities are
    normalised to sum 1."""
    routed: int
    held: int
    first: int
    per_token: int
    width: int
    normalise: bool
    # DeepSeek-V3's router and layer (``_deepseek_experts_of``); the
    # defaults are the softmax router with nothing beside it
    scoring: str = "softmax"    # 'softmax' | 'sigmoid' (float32 product)
    scale: float = 1.0          # ``routed_scaling_factor`` on the gates
    biased: bool = False        # ``noaux_tc``: the choice by score + bias
    balance: float = 0.0        # ``u`` of the balance part ``L_B``
    shared: int = 0             # the shared experts' width, together
    dense_first: int = 0        # leading layers whose feed-forward is dense


class Latent(NamedTuple):
    """Latent attention (``kv_lora_rank``): the key/value path's rank,
    the head's sizes without and with rotary embedding (a query/key
    head is both, side by side), the value head's size, and whether
    the rotary pairs are interleaved (``(2i, 2i + 1)``) in the
    weights' own layout."""
    rank: int
    nope: int
    rope: int
    value: int
    interleave: bool


class Selection(NamedTuple):
    """``sa_config``: the indexer's query heads and head size (one key
    head), the keys a query selects, the query rows a chunk."""
    heads: int
    head_dim: int
    topk: int
    chunk: int


class HybridSpec(NamedTuple):
    """The public config's keys that shape the model (hashable: the
    evaluation cache keys on it)."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    rms_norm_eps: float
    total_ut_steps: int = 1
    rope_theta: Optional[float] = None
    sandwich: bool = False      # the block: sandwich norms, no QK-norm
    exit_entropy_beta: float = 0.05
    num_key_value_heads: int = 0    # 0: as many as query heads
    head_dim: int = 0               # 0: hidden_size / query heads
    prenorm: bool = False       # the block: pre-norm
    experts: Optional[Experts] = None       # the layers' feed-forward
    selection: Optional[Selection] = None   # every full-attention layer
    embedding_init_std: float = INIT_STD    # the seeded embedding rows'
    head_norm: bool = False     # an RMSNorm over each head of q and k
    latent: Optional[Latent] = None     # every latent-attention layer

    @property
    def looped(self) -> bool:
        return self.total_ut_steps > 1

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads


def _experts_of(path: str, doc: dict) -> Optional[Experts]:
    """The expert layer of a file with ``num_experts``: the experts
    held here. The router's width is ``published.num_experts`` (the
    file's own count where nothing was cut) and the first expert held
    ``first_expert_held`` (default 0)."""
    held = int(doc.get("num_experts") or 0)
    if not held:
        return None
    missing = [k for k in EXPERT_KEYS if k not in doc]
    if missing:
        raise ValueError(f"model specification {path!r} lacks {missing}")
    if doc.get("mlp_only_layers") or doc.get("decoder_sparse_step", 1) != 1:
        raise ValueError(
            f"model specification {path!r}: mlp_only_layers / "
            "decoder_sparse_step ask for dense layers among the expert "
            "layers, which is not written (every layer an expert layer)")
    routed = int((doc.get("published") or {}).get("num_experts", held))
    first = int(doc.get("first_expert_held", 0))
    per_token = int(doc["num_experts_per_tok"])
    if first < 0 or first + held > routed or not 0 < per_token <= routed:
        raise ValueError(
            f"model specification {path!r}: experts {first}.."
            f"{first + held - 1} held and {per_token} a token do not lie "
            f"within the router's {routed}")
    return Experts(routed, held, first, per_token,
                   int(doc["moe_intermediate_size"]),
                   bool(doc.get("norm_topk_prob", False)))


def _deepseek_experts_of(path: str, doc: dict) -> Experts:
    """The expert layers of a ``deepseek_v3`` file: ``n_routed_experts``
    the experts held here, ``published.n_routed_experts`` the router's
    width (the file's own count where nothing was cut),
    ``first_expert_held`` the first one held; the first
    ``first_k_dense_replace`` layers dense. ``balance_loss_coef`` is
    ``u`` of the balance part (default 0: the bias then stays where it
    is)."""
    missing = [k for k in DEEPSEEK_EXPERT_KEYS + ("num_hidden_layers",)
               if k not in doc]
    if missing:
        raise ValueError(f"model specification {path!r} lacks {missing}")
    refusals = (
        ("moe_layer_freq", doc.get("moe_layer_freq", 1) != 1,
         "dense layers among the expert layers are not written "
         "(moe_layer_freq 1)"),
        ("n_group / topk_group",
         (doc.get("n_group", 1), doc.get("topk_group", 1)) != (1, 1),
         "grouped routing is not written (n_group 1, topk_group 1: the "
         "group step then selects everything)"),
        ("scoring_func", doc["scoring_func"] not in ("sigmoid", "softmax"),
         f"{doc['scoring_func']!r} is not written (sigmoid, softmax)"),
        ("topk_method", doc["topk_method"] != "noaux_tc",
         f"{doc['topk_method']!r} is not written (noaux_tc: the choice "
         "by score plus a per-expert bias)"),
    )
    for key, refused, why in refusals:
        if refused:
            raise ValueError(f"model specification {path!r}: {key}: {why}")
    held = int(doc["n_routed_experts"])
    routed = int((doc.get("published") or {}).get("n_routed_experts", held))
    first = int(doc.get("first_expert_held", 0))
    per_token = int(doc["num_experts_per_tok"])
    dense = int(doc["first_k_dense_replace"])
    if first < 0 or first + held > routed or not 0 < per_token <= routed \
            or not 0 <= dense <= int(doc["num_hidden_layers"]):
        raise ValueError(
            f"model specification {path!r}: experts {first}.."
            f"{first + held - 1} held and {per_token} a token do not lie "
            f"within the router's {routed}, or first_k_dense_replace "
            f"{dense} not within the layers")
    return Experts(
        routed, held, first, per_token, int(doc["moe_intermediate_size"]),
        bool(doc.get("norm_topk_prob", False)),
        scoring=doc["scoring_func"],
        scale=float(doc["routed_scaling_factor"]),
        biased=True,
        balance=float(doc.get("balance_loss_coef", 0.0)),
        shared=int(doc["n_shared_experts"])
        * int(doc["moe_intermediate_size"]),
        dense_first=dense)


def _latent_of(path: str, doc: dict) -> Latent:
    missing = [k for k in LATENT_KEYS if k not in doc]
    if missing:
        raise ValueError(f"model specification {path!r} lacks {missing}")
    if doc.get("q_lora_rank") is not None:
        raise ValueError(
            f"model specification {path!r}: q_lora_rank "
            f"{doc['q_lora_rank']}: a low-rank query path is not written "
            "(q_lora_rank null: one full query projection)")
    rope = int(doc["qk_rope_head_dim"])
    if rope % 2:
        raise ValueError(
            f"model specification {path!r}: qk_rope_head_dim {rope} is "
            "odd (the rotary embedding turns pairs)")
    return Latent(int(doc["kv_lora_rank"]), int(doc["qk_nope_head_dim"]),
                  rope, int(doc["v_head_dim"]),
                  bool(doc.get("rope_interleave", False)))


def _selection_of(path: str, doc: dict) -> Optional[Selection]:
    sa = doc.get("sa_config")
    if not sa:
        return None
    missing = [k for k in SELECTION_KEYS if k not in sa]
    if missing:
        raise ValueError(
            f"model specification {path!r}: sa_config lacks {missing}")
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError(
            f"model specification {path!r}: sa_config: the indexer is "
            "written with one key head (indexer_num_kv_heads 1)")
    if sa["topk"] % sa["q_chunk_size"]:
        raise ValueError(
            f"model specification {path!r}: sa_config: topk {sa['topk']} "
            f"is no multiple of q_chunk_size {sa['q_chunk_size']}")
    return Selection(int(sa["indexer_num_heads"]),
                     int(sa["indexer_head_dim"]), int(sa["topk"]),
                     int(sa["q_chunk_size"]))


def load_spec(path: str) -> HybridSpec:
    """Read a specification file. Keys beyond the public config's are
    ignored (a benchmark configuration's file carries its launcher
    flags beside them); ``layer_types`` is cut to
    ``num_hidden_layers`` (absent: every layer a ``full_attention``
    one); the ``linear_*`` keys are asked for only where a layer of the
    cut is a ``linear_attention`` one, ``intermediate_size`` only where
    the feed-forward is dense."""
    with open(path) as f:
        doc = json.load(f)
    deepseek = doc.get("model_type") == "deepseek_v3"
    latent = _latent_of(path, doc) if deepseek else None
    experts = _deepseek_experts_of(path, doc) if deepseek \
        else _experts_of(path, doc)
    if "num_hidden_layers" in doc:
        doc.setdefault("layer_types", [
            "latent_attention" if deepseek else "full_attention"]
            * int(doc["num_hidden_layers"]))
    if experts is not None and not experts.dense_first:
        doc.setdefault("intermediate_size", 0)
    missing = [k for k in PLAIN_KEYS + ("num_hidden_layers",)
               if k not in doc]
    if missing:
        raise ValueError(f"model specification {path!r} lacks {missing}")
    kinds = tuple(doc["layer_types"][:int(doc["num_hidden_layers"])])
    if len(kinds) != int(doc["num_hidden_layers"]) \
            or any(k not in LAYER_KINDS for k in kinds):
        raise ValueError(
            f"model specification {path!r}: layer_types must name "
            f"num_hidden_layers layers, each one of {LAYER_KINDS}")
    if ("latent_attention" in kinds) != (
            latent is not None and len(set(kinds)) == 1):
        raise ValueError(
            f"model specification {path!r}: latent_attention layers are "
            "written for model_type 'deepseek_v3' (kv_lora_rank ...), "
            "whose every layer is one")
    linear = "linear_attention" in kinds
    missing = [k for k in LINEAR_KEYS if linear and k not in doc]
    if missing:
        raise ValueError(f"model specification {path!r} lacks {missing}")
    heads = int(doc["num_attention_heads"])
    kv_heads = int(doc.get("num_key_value_heads") or heads)
    if heads % kv_heads:
        raise ValueError(
            f"model specification {path!r}: num_attention_heads {heads} "
            f"is no multiple of num_key_value_heads {kv_heads}")
    if doc.get("linear_num_value_heads") \
            != doc.get("linear_num_key_heads"):
        raise ValueError(
            f"model specification {path!r}: grouped value heads in the "
            "linear-attention layers are not supported (as many value "
            "heads as key heads)")
    if doc.get("tie_word_embeddings") or doc.get("attention_bias"):
        raise ValueError(
            f"model specification {path!r}: tied embeddings and "
            "attention biases are not supported")
    head_dim = int(doc.get("head_dim") or doc["hidden_size"] // heads)
    if head_dim % 2:
        raise ValueError(
            f"model specification {path!r}: head_dim {head_dim} is odd "
            "(the rotary embedding turns pairs)")
    steps = int(doc.get("total_ut_steps", 1))
    sandwich = doc.get("model_type") in SANDWICH_BLOCKS
    if steps < 1 or (steps > 1 and not sandwich):
        raise ValueError(
            f"model specification {path!r}: total_ut_steps {steps} with "
            f"model_type {doc.get('model_type')!r}: a looped stack is "
            f"written for {SANDWICH_BLOCKS} only")
    scaling = doc.get("rope_scaling")
    if scaling and (set(scaling) - MROPE_KEYS or {
            scaling.get("rope_type", "default"),
            scaling.get("type", "default")} != {"default"}
            or sum(scaling.get("mrope_section", [head_dim // 2]))
            != head_dim // 2):
        raise ValueError(
            f"model specification {path!r}: rope_scaling is not supported "
            "(but for the default embedding's mrope_section record, whose "
            "sections fill half a head)")
    selection = _selection_of(path, doc)
    prenorm = doc.get("model_type") in PRENORM_BLOCKS
    if (experts or selection) and not prenorm:
        raise ValueError(
            f"model specification {path!r}: num_experts and sa_config are "
            f"written for the pre-norm block ({PRENORM_BLOCKS}), not for "
            f"model_type {doc.get('model_type')!r}")
    theta = doc.get("rope_theta",
                    (doc.get("rope_parameters") or {}).get("rope_theta"))
    embed_std = float(doc.get("embedding_init_std", INIT_STD))
    if not 0.0 < embed_std < math.inf:
        raise ValueError(
            f"model specification {path!r}: embedding_init_std "
            f"{embed_std} is no positive size")
    fields = dict({k: doc[k] for k in PLAIN_KEYS}, layer_types=kinds,
                  **{k: doc.get(k, 0) for k in LINEAR_KEYS})
    return HybridSpec(
        **fields, total_ut_steps=steps,
        rope_theta=None if theta is None else float(theta),
        sandwich=sandwich,
        exit_entropy_beta=float(doc.get("exit_entropy_beta", 0.05)),
        num_key_value_heads=kv_heads, head_dim=head_dim,
        prenorm=prenorm, experts=experts, selection=selection,
        embedding_init_std=embed_std,
        head_norm=doc.get("model_type") in HEAD_NORM_BLOCKS,
        latent=latent)


def _linear_shapes(s: HybridSpec) -> dict:
    d, h = s.hidden_size, s.linear_num_key_heads
    qk, vv = h * s.linear_key_head_dim, h * s.linear_value_head_dim
    return {"wq": (d, qk), "wk": (d, qk), "wv": (d, vv), "wg": (d, vv),
            "wo": (vv, d), "wa": (d, h), "wb": (d, h),
            "conv": (2 * qk + vv, s.linear_conv_kernel_dim),
            "a_log": (h,), "dt_bias": (h,),
            "o_norm": (s.linear_value_head_dim,)}


def _full_shapes(s: HybridSpec) -> dict:
    d, hd = s.hidden_size, s.head_size
    q, kv = s.num_attention_heads * hd, s.kv_heads * hd
    proj = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    # lint: disable=FTL005 — the block is a flag of the spec
    if s.sandwich:
        return proj
    # lint: disable=FTL005 — the block is a flag of the spec
    if s.head_norm:
        proj.update(q_norm=(hd,), k_norm=(hd,))     # over each head
    else:
        proj.update(q_norm=(q,), k_norm=(kv,))      # over all of q, of k
    sel = s.selection
    # lint: disable=FTL005 — sa_config or none, by the spec
    if sel is not None:
        proj.update(index_q=(d, sel.heads * sel.head_dim),
                    index_k=(d, sel.head_dim), index_w=(d, sel.heads))
    return proj


def _latent_shapes(s: HybridSpec) -> dict:
    d, h, l = s.hidden_size, s.num_attention_heads, s.latent
    return {"wq": (d, h * (l.nope + l.rope)),
            "wkv_a": (d, l.rank + l.rope), "kv_a_norm": (l.rank,),
            "wkv_b": (l.rank, h * (l.nope + l.value)),
            "wo": (h * l.value, d)}


def _mixer_shapes(s: HybridSpec, kind: str) -> dict:
    return {"linear_attention": _linear_shapes,
            "latent_attention": _latent_shapes,
            "full_attention": _full_shapes}[kind](s)


def dense_layer(s: HybridSpec, i: int) -> bool:
    """Layer ``i``'s feed-forward is a dense SwiGLU (every layer's
    without experts; the first ``first_k_dense_replace`` with)."""
    return s.experts is None or i < s.experts.dense_first


def _swiglu_shapes(d: int, f: int) -> dict:
    return {"gate": (d, f), "up": (d, f), "down": (f, d)}


def _mlp_shapes(s: HybridSpec, dense: bool = True) -> dict:
    d, e = s.hidden_size, s.experts
    # lint: disable=FTL005 — experts or a dense feed-forward, by the spec
    if e is None or dense:
        return _swiglu_shapes(d, s.intermediate_size)
    # the experts held here are one stacked leaf a matrix
    tree = {"router": (d, e.routed), "gate": (e.held, d, e.width),
            "up": (e.held, d, e.width), "down": (e.held, e.width, d)}
    # lint: disable=FTL005 — flags of the spec
    if e.biased:
        tree["router_bias"] = (e.routed,)
    # lint: disable=FTL005 — a width of the spec
    if e.shared:
        tree["shared"] = _swiglu_shapes(d, e.shared)
    return tree


def param_shapes(s: HybridSpec) -> dict:
    d = s.hidden_size
    tree = {"embed": (s.vocab_size, d), "final_norm": (d,),
            "head": (d, s.vocab_size)}
    # lint: disable=FTL005 — a looped model or not, by the spec
    if s.looped:
        tree["exit_gate"] = {"w": (d,), "b": (1,)}
    for i, kind in enumerate(s.layer_types):
        tree[f"layer_{i}"] = {
            "mixer": _mixer_shapes(s, kind),
            "mixer_norm": (d,), "mlp_norm": (d,),
            "mlp": _mlp_shapes(s, dense_layer(s, i))}
        # lint: disable=FTL005 — the block is a flag of the spec
        if s.sandwich:
            tree[f"layer_{i}"].update(mixer_in_norm=(d,), mlp_in_norm=(d,))
    return tree


def init_params(spec: HybridSpec, rng) -> Any:
    """Seeded float32 parameters: matrices normal(0, 0.02) (the
    embedding's rows ``embedding_init_std`` where the file gives it),
    norm scales 1, the exit gate's and the routers' biases 0, the
    convolution
    uniform(+-1/sqrt(taps)), decay rates ``exp(a_log)`` spread over
    1..16 and time steps ``softplus(dt_bias)`` log-spread over
    0.001..0.1 across the heads (the delta-rule family's own
    initialisation)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        key = jax.random.fold_in(rng, i)
        if name.endswith("norm"):
            leaf = jnp.ones(shape, jnp.float32)
        elif name in ("b", "router_bias"):
            leaf = jnp.zeros(shape, jnp.float32)
        elif name == "a_log":
            leaf = jnp.log(jnp.linspace(1.0, 16.0, shape[0]))
        elif name == "dt_bias":
            dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(0.1),
                                      shape[0]))
            leaf = dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1
        elif name == "conv":
            bound = 1.0 / math.sqrt(shape[1])
            leaf = jax.random.uniform(key, shape, jnp.float32, -bound,
                                      bound)
        else:
            std = spec.embedding_init_std if name == "embed" else INIT_STD
            leaf = std * jax.random.normal(key, shape, jnp.float32)
        out.append(leaf.astype(jnp.float32))
    return jax.tree.unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _jitted_init(spec: HybridSpec):
    """One program for the whole tree, traced once a specification."""
    # lint: disable=FTL004 — a key goes in and the parameters come out
    return jax.jit(functools.partial(init_params, spec))


# -- the forward pass -------------------------------------------------------

def _rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _l2_normalize(x):
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _causal_conv(x, w):
    """Depthwise over time, float32: ``x`` [B, T, C], ``w`` [C, taps]."""
    taps, T = w.shape[1], x.shape[1]
    pad = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(pad[:, i:i + T] * w[:, i] for i in range(taps))


def _dot(x, w, dt, name: Optional[str] = None):
    """``x @ w``: operands in the compute dtype, float32 out. ``name``
    is the result's name for a rematerialized layer's policy
    (:func:`kept_products`); it changes nothing of the product."""
    out = jnp.matmul(x.astype(dt), w.astype(dt),
                     preferred_element_type=jnp.float32)
    return out if name is None else checkpoint_name(out, name)


def _dot32(x, w, name: str):
    """``x @ w`` in float32 at the highest precision (DeepSeek-V3's
    router: the published code runs that product in float32), the
    result named as :func:`_dot`'s."""
    out = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return checkpoint_name(out, name)


def rotary_tables(positions, head_dim: int, theta: float):
    """(cos, sin), each ``[T, head_dim]`` float32, of the rotate-half
    rotary embedding at ``positions`` [T]: pair ``i`` of a head
    (elements ``i`` and ``i + head_dim / 2``) turns by
    ``position * theta ** (-2 i / head_dim)``."""
    inv = jnp.exp(jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                  * (-math.log(theta) / head_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, rope):
    """``x`` [B, T, H, hd] float32 turned by ``rope``'s angles."""
    cos, sin = (t[None, :, None, :] for t in rope)
    a, b = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


def pairs_apart(x):
    """The last axis' interleaved pairs ``(2i, 2i + 1)`` laid out as
    rotate-half has them, ``(i, i + n/2)``: the even elements, then the
    odd ones. Applied to a query's and to a key's rotary part alike it
    leaves every dot product where it was, so the interleaved rotary
    embedding (``rope_interleave``) of the published weights is
    ``_rotate`` of this layout."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _linear_attention(p, x, s: HybridSpec, dt):
    B, T, _ = x.shape
    h, dk, dv = (s.linear_num_key_heads, s.linear_key_head_dim,
                 s.linear_value_head_dim)
    qkv = jnp.concatenate([_dot(x, p["w" + n], dt, "mixer." + n)
                           for n in "qkv"], axis=-1)
    qkv = jax.nn.silu(_causal_conv(qkv, p["conv"]))
    q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
    q = _l2_normalize(q.reshape(B, T, h, dk)) / math.sqrt(dk)
    k = _l2_normalize(k.reshape(B, T, h, dk))
    v = v.reshape(B, T, h, dv)
    # the factor 2 is the negative eigenvalue the config allows
    beta = 2.0 * jax.nn.sigmoid(_dot(x, p["wb"], dt, "mixer.b"))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
        _dot(x, p["wa"], dt, "mixer.a") + p["dt_bias"])
    with jax.named_scope("lm.delta_rule"):
        o = chunk_gated_delta_rule(q.astype(dt), k.astype(dt),
                                   v.astype(dt), g, beta)
    o = _rms_norm(o, p["o_norm"], s.rms_norm_eps)
    gate = jax.nn.silu(_dot(x, p["wg"], dt, "mixer.g")).reshape(
        B, T, h, dv)
    return _dot((o * gate).reshape(B, T, h * dv), p["wo"], dt, "mixer.o")


def _projected(p, x, s: HybridSpec, dt, rope, out):
    """q [B, T, H, hd], k and v [B, T, KV, hd] of a full-attention
    layer, of type ``out``: projected, normed as the block has it, q
    and k turned by ``rope`` where the model has one."""
    B, T, _ = x.shape
    hd = s.head_size

    def project(name, norm, n):
        t = _dot(x, p[name], dt, "mixer." + name[1:])
        # lint: disable=FTL005 — the block is a flag of the spec
        if norm and s.head_norm:
            return _rms_norm(t.reshape(B, T, n, hd), p[norm],
                             s.rms_norm_eps).reshape(B, T, n * hd)
        # lint: disable=FTL005 — the block is a flag of the spec
        if norm and not s.sandwich:
            t = _rms_norm(t, p[norm], s.rms_norm_eps)
        return t

    def heads(t, n, turn):
        # lint: disable=FTL005 — a model with or without rotary embedding
        if turn and rope is not None:
            return _rotate(t.reshape(B, T, n, hd), rope).astype(out)
        return t.astype(out).reshape(B, T, n, hd)

    h, kv = s.num_attention_heads, s.kv_heads
    q, k = project("wq", "q_norm", h), project("wk", "k_norm", kv)
    v = project("wv", None, kv)
    return heads(q, h, True), heads(k, kv, True), heads(v, kv, False)


def _softmax_attention(q, k, v, dt, attention: str):
    """Causal softmax attention of ``q``, ``k`` [B, T, H, hd] and ``v``
    [B, T, H, vd] (``vd`` the value head's own size) -> float32 or
    ``dt`` [B, T, H, vd], scores scaled by ``hd ** -0.5``: the flash
    kernel or the dense form, by ``attention`` and ``T``."""
    T, hd = q.shape[1], q.shape[-1]
    with jax.named_scope("lm.attention"):
        # lint: disable=FTL005 — a static mode string and a static length
        if resolve_attention(attention, T) == "flash":
            from fedtorch_tpu.ops.pallas.flash_attention import (
                flash_attention,
            )
            return flash_attention(q, k, v, causal=True)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(hd)
        mask = jnp.tril(jnp.ones((T, T), bool))
        probs = jax.nn.softmax(
            jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dt), v,
                          preferred_element_type=jnp.float32)


def _full_attention(p, x, s: HybridSpec, dt, attention: str, rope=None):
    B, T, _ = x.shape
    h, hd = s.num_attention_heads, s.head_size
    q, k, v = _projected(p, x, s, dt, rope, dt)
    # lint: disable=FTL005 — head counts of the spec
    if s.kv_heads != h:
        # grouped heads: query head i reads key head i // (h / kv)
        k, v = (jnp.repeat(t, h // s.kv_heads, axis=2) for t in (k, v))
    out = _softmax_attention(q, k, v, dt, attention)
    return _dot(out.reshape(B, T, h * hd), p["wo"], dt, "mixer.o")


def _latent_attention(p, x, s: HybridSpec, dt, attention: str, rope):
    """A latent-attention layer (DeepSeek-V2/V3's, without the low-rank
    query path): ``q = W_q u`` in H heads of ``nope + rope``;
    ``[c | k_r] = W_a u``, ``c`` the ``rank``-wide latent, which gets an
    RMSNorm of its own, and ``k_r`` ONE rotary key head shared by all
    query heads; ``[k_n | v] = W_b c'`` in H heads of ``nope + value``;
    the rotary parts turned (interleaved pairs where the file says so),
    ``k = [k_n | k_r]``; causal softmax of ``q . k / sqrt(nope + rope)``
    over value heads of ``value``; ``W_o``. All of it but the softmax
    attention runs under the scope ``lm.latent``."""
    B, T, _ = x.shape
    h, l = s.num_attention_heads, s.latent
    with jax.named_scope("lm.latent"):
        q = _dot(x, p["wq"], dt, "mixer.q").reshape(B, T, h,
                                                    l.nope + l.rope)
        a = _dot(x, p["wkv_a"], dt, "mixer.kv_a")
        c = _rms_norm(a[..., :l.rank], p["kv_a_norm"], s.rms_norm_eps)
        kv = _dot(c, p["wkv_b"], dt, "mixer.kv_b").reshape(
            B, T, h, l.nope + l.value)
        q_r, k_r = q[..., l.nope:], a[..., l.rank:].reshape(B, T, 1, l.rope)
        # lint: disable=FTL005 — a flag of the spec
        if l.interleave:
            q_r, k_r = pairs_apart(q_r), pairs_apart(k_r)
        q_r, k_r = _rotate(q_r, rope), _rotate(k_r, rope)
        q = jnp.concatenate([q[..., :l.nope], q_r], axis=-1).astype(dt)
        k = jnp.concatenate(
            [kv[..., :l.nope], jnp.broadcast_to(k_r, (B, T, h, l.rope))],
            axis=-1).astype(dt)
        v = kv[..., l.nope:].astype(dt)
    out = _softmax_attention(q, k, v, dt, attention)
    with jax.named_scope("lm.latent"):
        return _dot(out.reshape(B, T, h * l.value), p["wo"], dt, "mixer.o")


def _selected_attention(p, x, s: HybridSpec, dt, rope):
    """A full-attention layer under ``sa_config``
    (``ops/sparse_attention.py``): (the layer's output, ``L_I``). The
    indexer reads ``x`` under ``stop_gradient``, so that the KL term
    trains its three matrices and nothing else; its queries and key
    take the rotary embedding over their own head size, and its
    weights the scale ``(heads x head_dim) ** -0.5``."""
    B, T, _ = x.shape
    sel, h, hd = s.selection, s.num_attention_heads, s.head_size
    # float32 into the chunks, which cast them: the chunks' cotangents
    # of k and v then add up in float32
    q, k, v = _projected(p, x, s, dt, rope, jnp.float32)
    with jax.named_scope("lm.indexer"):
        u = jax.lax.stop_gradient(x)
        qi = _dot(u, p["index_q"], dt, "mixer.index_q").reshape(
            B, T, sel.heads, sel.head_dim)
        ki = _dot(u, p["index_k"], dt, "mixer.index_k").reshape(
            B, T, 1, sel.head_dim)
        wi = _dot(u, p["index_w"], dt, "mixer.index_w") \
            * (sel.heads * sel.head_dim) ** -0.5
        # lint: disable=FTL005 — a model with or without rotary embedding
        if rope is not None:
            turn = rotary_tables(jnp.arange(T), sel.head_dim, s.rope_theta)
            qi, ki = _rotate(qi, turn), _rotate(ki, turn)
    out, index_loss = sparse_attention.selected_attention(
        q, k, v, qi, ki[:, :, 0], wi, topk=sel.topk, chunk=sel.chunk,
        dt=dt, scopes=("lm.indexer", "lm.attention"))
    return _dot(out.reshape(B, T, h * hd), p["wo"], dt, "mixer.o"), \
        index_loss


def _experts(p, x, s: HybridSpec, dt):
    """The expert layer's share (``ops/routed_experts.py``): (its
    result [B, T, D], the layer call's routing counters). DeepSeek-V3's
    layer (sigmoid scores from a float32 product, the choice by score
    plus ``router_bias``) also counts the step's pairs over ALL routed
    experts, whose largest over their mean and the balance part of the
    loss (``routed_experts.balance_step``) join the counters, and adds
    the shared expert's SwiGLU, which every chip computes alike."""
    B, T, d = x.shape
    e = s.experts
    u = x.reshape(B * T, d)
    with jax.named_scope("lm.router"):
        # lint: disable=FTL005 — the router's kind, by the spec
        if e.biased:
            bias = p["router_bias"]
            gates, chosen = routed_experts.route(
                _dot32(u, p["router"], "mlp.router"), e.per_token,
                e.normalise, scoring=e.scoring, bias=bias, scale=e.scale)
        else:
            gates, chosen = routed_experts.route(
                _dot(u, p["router"], dt, "mlp.router"), e.per_token,
                e.normalise)
    out, counters = routed_experts.expert_share(
        p, u, gates, chosen, first=e.first, dt=dt,
        block=routed_experts.block_rows(B * T, e.per_token, e.held,
                                        e.routed),
        scopes=("lm.router", "lm.experts"))
    # lint: disable=FTL005 — flags of the spec
    if e.biased:
        with jax.named_scope("lm.router"):
            load = routed_experts.load_over_all(chosen, e.routed)
            counters.update(
                router_load_max_over_mean=jnp.max(load) / jnp.mean(load),
                router_bias_abs_max=jnp.max(jnp.abs(bias)),
                balance=routed_experts.balance_step(bias, load))
    # lint: disable=FTL005 — a width of the spec
    if e.shared:
        out = out + _mlp(p["shared"], u, dt, "shared")
    return out.reshape(B, T, d), counters


def _mlp(p, x, dt, name: str = "mlp"):
    """A SwiGLU under the scope ``lm.<name>``, its products' results
    named ``<name>.gate`` / ``up`` / ``down`` (``dense``: a dense layer
    of a model whose other layers hold experts, whose grouped products
    carry the ``mlp.`` names; its scope is ``lm.mlp``)."""
    # lint: disable=FTL005 — a name the caller writes out
    scope = "lm.mlp" if name == "dense" else "lm." + name
    with jax.named_scope(scope):
        return _dot(jax.nn.silu(_dot(x, p["gate"], dt, name + ".gate"))
                    * _dot(x, p["up"], dt, name + ".up"), p["down"], dt,
                    name + ".down")


def _layer(p, x, kind: str, s: HybridSpec, dt, attention: str, rope=None):
    eps = s.rms_norm_eps

    def mixer(u):
        # lint: disable=FTL005 — the layer's kind is a string of the spec
        if kind == "linear_attention":
            return _linear_attention(p["mixer"], u, s, dt)
        return _full_attention(p["mixer"], u, s, dt, attention, rope)

    # the residual stream stays float32: a sublayer's normed output is
    # of unit size beside an embedding of 0.02, and bfloat16's spacing
    # near 1 would round a tenth of the embedding away
    # lint: disable=FTL005 — the block is a flag of the spec
    if s.sandwich:
        x = x + _rms_norm(mixer(_rms_norm(x, p["mixer_in_norm"], eps)),
                          p["mixer_norm"], eps)
        return x + _rms_norm(
            _mlp(p["mlp"], _rms_norm(x, p["mlp_in_norm"], eps), dt),
            p["mlp_norm"], eps)
    x = x + _rms_norm(mixer(x), p["mixer_norm"], eps)
    return x + _rms_norm(_mlp(p["mlp"], x, dt), p["mlp_norm"], eps)


def _prenorm_layer(p, x, kind: str, s: HybridSpec, dt, attention: str,
                   rope, dense: bool):
    """A pre-norm layer, ``a = x + Mixer(N_1(x))``, ``y = a +
    FF(N_2(a))``: (``y``, the layer call's parts: ``index_loss`` under
    ``sa_config``, the routing counters of an expert layer). ``dense``
    (:func:`dense_layer`): this layer's feed-forward is a dense
    SwiGLU."""
    eps, parts = s.rms_norm_eps, {}
    u = _rms_norm(x, p["mixer_norm"], eps)
    # lint: disable=FTL005 — the layer's kind and sa_config, by the spec
    if kind == "linear_attention":
        x = x + _linear_attention(p["mixer"], u, s, dt)
    # lint: disable=FTL005 — the layer's kind is a string of the spec
    elif kind == "latent_attention":
        x = x + _latent_attention(p["mixer"], u, s, dt, attention, rope)
    # lint: disable=FTL005 — sa_config or none, by the spec
    elif s.selection is not None:
        o, parts["index_loss"] = _selected_attention(p["mixer"], u, s, dt,
                                                     rope)
        x = x + o
    else:
        x = x + _full_attention(p["mixer"], u, s, dt, attention, rope)
    u = _rms_norm(x, p["mlp_norm"], eps)
    # lint: disable=FTL005 — experts or a dense feed-forward, by the spec
    if not dense:
        o, counters = _experts(p["mlp"], u, s, dt)
        parts.update(counters)
        return x + o, parts
    return x + _mlp(p["mlp"], u, dt,
                    "mlp" if s.experts is None else "dense"), parts


def _rope(s: HybridSpec, T: int):
    """The rotary tables of a ``T``-token row, or None without
    ``rope_theta``."""
    if s.rope_theta is None:
        return None
    turned = s.head_size if s.latent is None else s.latent.rope
    return rotary_tables(jnp.arange(T), turned, s.rope_theta)


# -- what a rematerialized layer keeps ---------------------------------------

def layer_products(s: HybridSpec, kind: str,
                   dense: Optional[bool] = None) -> dict:
    """``{name: (K, N)}`` of a layer's matrix products ``[T, K] x
    [K, N]``, in the layer's own order: the names their results carry
    (``_dot``'s ``name``). ``dense`` (default: the model has no
    experts): the layer's feed-forward is a dense SwiGLU, named
    ``dense.*`` in a model whose other layers hold experts. An expert
    layer's ``mlp.gate`` / ``up`` / ``down`` are grouped products over
    the dispatch buffer's row blocks in use, ``[block, K] x [K, N]`` an
    expert, the names on the first block's results
    (:func:`_product_cost`); its shared expert's are ``shared.*``."""
    d, e = s.hidden_size, s.experts
    dense = e is None if dense is None else dense
    # lint: disable=FTL005 — the layer's kind is a string of the spec
    if kind == "linear_attention":
        shapes = _linear_shapes(s)
        mixer = {"mixer." + n: shapes["w" + n] for n in "qkvbago"}
    # lint: disable=FTL005 — the layer's kind is a string of the spec
    elif kind == "latent_attention":
        shapes = _latent_shapes(s)
        mixer = {"mixer." + n: shapes["w" + n]
                 for n in ("q", "kv_a", "kv_b", "o")}
    else:
        shapes = _full_shapes(s)
        mixer = {"mixer." + n: shapes["w" + n] for n in "qkvo"}
        mixer.update({"mixer." + n: shapes[n]
                      for n in ("index_q", "index_k", "index_w")
                      if n in shapes})
    swiglu = lambda name, f: {name + ".gate": (d, f), name + ".up": (d, f),
                              name + ".down": (f, d)}
    # lint: disable=FTL005 — experts or a dense feed-forward, by the spec
    if dense:
        return dict(mixer, **swiglu("mlp" if e is None else "dense",
                                    s.intermediate_size))
    mixer["mlp.router"] = (d, e.routed)
    mixer.update(swiglu("mlp", e.width))
    # lint: disable=FTL005 — a width of the spec
    if e.shared:
        mixer.update(swiglu("shared", e.shared))
    return mixer


def _product_cost(s: HybridSpec, name: str, k: int, n: int, step: int):
    """(FLOPs, float32 result elements) a token of one product, in a
    step of ``step`` tokens. An expert layer's grouped products run the
    expected ``per_token x held / routed`` buffer rows a token, and
    what carries their names is the first row block's results
    (``routed_experts.block_rows`` of the step's tokens: further
    blocks, where more is routed here, are computed again in the
    backward pass), whatever is routed here."""
    e = s.experts
    # lint: disable=FTL005 — host integers of the spec
    if e is not None and name in ("mlp.gate", "mlp.up", "mlp.down"):
        block = routed_experts.block_rows(step, e.per_token, e.held,
                                          e.routed)
        return (2 * k * n * e.per_token * e.held / e.routed,
                n * block / step)
    return 2 * k * n, n


def _layers_products(s: HybridSpec):
    """Each layer's :func:`layer_products`, in the stack's order."""
    return [layer_products(s, kind, dense_layer(s, i))
            for i, kind in enumerate(s.layer_types)]


def _product_totals(s: HybridSpec, step: int) -> dict:
    """``{name: (FLOPs, float32 result bytes)}`` a token and pass of a
    step of ``step`` tokens, summed over the layers that have a product
    of that name."""
    totals: dict = {}
    for products in _layers_products(s):
        for name, (k, n) in products.items():
            flops, size = totals.get(name, (0, 0))
            cost, floats = _product_cost(s, name, k, n, step)
            totals[name] = (flops + cost, size + 4 * floats)
    return totals


def kept_products(s: HybridSpec, rows: int, tokens: int,
                  budget: Optional[int]) -> Tuple[str, ...]:
    """The names of the products whose float32 results the
    rematerialized layers of a ``rows x tokens`` step keep, within
    ``budget`` bytes (None: no limit known, all of them). A name stands
    for that product in every layer call of the step (layers x
    ``total_ut_steps``: the residuals of all of them are alive when
    the backward pass begins). In order of FLOPs spared a byte kept,
    which for ``[T, K] x [K, N]`` is ``K / 2``: the longest inner
    dimension first; of equal ones a sublayer's output before its
    inputs (``SUBLAYER_OUTPUTS``), then in the layer's own order; each
    taken if what is left of the budget holds it. From shapes alone:
    the same answer for the same arguments."""
    totals = _product_totals(s, rows * tokens)
    order = sorted(totals, key=lambda n: (
        -totals[n][0] / totals[n][1], n not in SUBLAYER_OUTPUTS))
    if budget is None:
        return tuple(order)
    calls = rows * tokens * s.total_ut_steps
    kept = []
    for name in order:
        size = totals[name][1] * calls
        # lint: disable=FTL005 — host integers from shapes
        if size <= budget:
            kept.append(name)
            budget -= size
    return tuple(kept)


def kept_counters(s: HybridSpec, tokens: int, kept, rows: int = 1) -> dict:
    """The row's two counters of how far the policy engaged:
    ``lm_kept_product_share``, the share of the stack's forward product
    FLOPs whose results are kept, and ``lm_kept_residual_bytes``, what
    they hold a sequence of ``tokens`` (of a step of ``rows``)."""
    totals = _product_totals(s, rows * tokens)
    flops, size = (sum(totals[n][i] for n in kept) for i in (0, 1))
    return {"lm_kept_product_share":
            flops / sum(t[0] for t in totals.values()),
            "lm_kept_residual_bytes":
            float(size * tokens * s.total_ut_steps)}


def residual_budget(s: HybridSpec, rows: int, tokens: int,
                    stats: Optional[dict]) -> Optional[int]:
    """Bytes a ``rows x tokens`` training step can give to kept
    products, from a device's ``memory_stats()`` as the step is traced
    (None, as the CPU gives: no limit known). ``bytes_limit`` less

    * ``bytes_in_use``: what the round holds before it starts (the
      server's tree, the clients' store);
    * two more parameter trees (the running client and the cohort's
      running sum) and, in a looped model, six bytes a looped
      parameter (the float32 accumulator of its gradient over the
      passes and the bfloat16 copy the scan over passes closes over),
      from the specification's own shapes;
    * the reserve: every layer call's kept input, and ``WORKING_SETS``
      times the widest layer's product results and the head's logits,
      for the one layer and the one head whose forward and backward
      pass are alive at a time; under ``sa_config`` as many times a
      query chunk's float32 scores (the heads' and the indexer's), and
      every layer's attention output (``sparse_attention.KEPT[1]``,
      kept whatever the budget).

    Read against the chip's allocator (``peak_bytes_reserved``, v5e,
    PERF.md section 6, PR 38): beside the sets this budget admits 1.1
    to 2.1 GiB of the device stay free; sets a gibibyte over it failed
    at compile by 150 MiB or ran a twentieth slower than none."""
    # lint: disable=FTL005 — the allocator's dict, read on the host
    if not stats or "bytes_limit" not in stats:
        return None
    count = lambda tree: sum(math.prod(shape) for shape in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    shapes = param_shapes(s)
    held = 2 * 4 * count(shapes)
    # lint: disable=FTL005 — a looped model or not, by the spec
    if s.looped:
        held += 6 * count([v for k, v in shapes.items()
                           if k.startswith("layer_")])
    widest = max(sum(_product_cost(s, name, k, n, rows * tokens)[1]
                     for name, (k, n) in products.items())
                 for products in _layers_products(s))
    reserve = 4 * rows * tokens * (
        s.hidden_size * len(s.layer_types) * s.total_ut_steps
        + WORKING_SETS * (widest + s.vocab_size))
    sel = s.selection
    # lint: disable=FTL005 — sa_config or none, by the spec
    if sel is not None:
        # a query chunk's float32 scores, of the heads and the indexer's,
        # and what every selected layer keeps whatever the budget: the
        # attention's output
        reserve += 4 * rows * tokens * (
            WORKING_SETS * min(tokens, sel.chunk)
            * (s.num_attention_heads + sel.heads)
            + len(s.layer_types) * s.num_attention_heads * s.head_size)
    return max(0, stats["bytes_limit"] - stats.get("bytes_in_use", 0)
               - held - reserve)


@functools.lru_cache(maxsize=None)
def _kept_for(s: HybridSpec, rows: int, tokens: int) -> Tuple[str, ...]:
    """:func:`kept_products` within what the fullest local device has
    left now. One decision a shape and process: every later trace of
    the step (the cost capture's twin, a retrace) builds the program
    the first one built, and the row's counters say what it holds.
    Several processes would each decide from their own devices and
    must build one program: they keep nothing."""
    # lint: disable=FTL005 — the process count is a host integer
    if jax.process_count() > 1:
        return ()
    budgets = [residual_budget(s, rows, tokens, d.memory_stats())
               for d in jax.local_devices()]
    # lint: disable=FTL005 — host integers or None
    budget = None if None in budgets else min(budgets)
    return kept_products(s, rows, tokens, budget)


def _stack(params, h, s: HybridSpec, dt, attention: str, remat: bool,
           rope):
    """One pass over the layers: ``[B, T, D] -> [B, T, D]``; of a
    pre-norm block also the layer calls' parts, each stacked ``[n]``
    over the layers."""
    # lint: disable=FTL005 — remat is a static flag of the launcher
    kept = _kept_for(s, h.shape[0], h.shape[1]) if remat else ()
    # lint: disable=FTL005 — remat and sa_config are static
    if remat and s.selection is not None:
        # the attention's output: the backward pass then runs the
        # dense chunks' attention once, not twice (a threshold is
        # never kept without the scores it is compared with:
        # ops/sparse_attention.py says why); of the fused form the
        # name holds all that the backward rule reads of the forward
        # pass, and no forward sweep runs again
        kept += sparse_attention.KEPT[1:]
    only = jax.checkpoint_policies.save_only_these_names
    # lint: disable=FTL005 — names or none: then the bare checkpoint
    policy = only(*kept) if kept else None
    parts = []
    for i, kind in enumerate(s.layer_types):
        # lint: disable=FTL005 — the block is a flag of the spec
        if s.prenorm:
            fn = lambda p, h, kind=kind, dense=dense_layer(s, i): \
                _prenorm_layer(p, h, kind, s, dt, attention, rope, dense)
        else:
            fn = lambda p, h, kind=kind: _layer(p, h, kind, s, dt,
                                                attention, rope)
        # lint: disable=FTL005 — remat is a static flag of the launcher
        h = (jax.checkpoint(fn, policy=policy) if remat else fn)(
            params[f"layer_{i}"], h)
        # lint: disable=FTL005 — the block is a flag of the spec
        if s.prenorm:
            h, part = h
            parts.append(part)
    # lint: disable=FTL005 — the block is a flag of the spec
    if s.prenorm:
        # each part stacked over the layers that report it (a dense
        # layer among expert layers reports no routing)
        return h, {key: jnp.stack([part[key] for part in parts
                                   if key in part])
                   for key in sorted(set().union(*parts))}
    return h


def hidden_states(params, x, s: HybridSpec, dt, attention: str,
                  remat: bool):
    """Token ids ``[B, T]`` -> the last layer's output ``[B, T, D]``
    of a single-pass model (the final norm is ``logits_of``'s); of a
    pre-norm block (the output, the layers' parts)."""
    return _stack(params, params["embed"][x], s, dt, attention, remat,
                  _rope(s, x.shape[1]))


def looped_states(params, x, s: HybridSpec, dt, attention: str,
                  remat: bool):
    """Token ids ``[B, T]`` -> every pass's output after the final
    norm, ``[R, B, T, D]``: one traced body of the layers, scanned
    ``total_ut_steps`` times with the parameters closed over, so a
    layer's gradient comes out as the sum over the passes."""
    rope = _rope(s, x.shape[1])

    def one_pass(h, _):
        h = _rms_norm(_stack(params, h, s, dt, attention, remat, rope),
                      params["final_norm"], s.rms_norm_eps)
        return h, h

    with jax.named_scope("lm.loop"):
        _, hs = jax.lax.scan(one_pass, params["embed"][x], None,
                             length=s.total_ut_steps)
    return hs


def logits_of(params, h, s: HybridSpec, dt):
    h = _rms_norm(h, params["final_norm"], s.rms_norm_eps)
    return _dot(h, params["head"], dt)


def next_token_stats(logits, x):
    """Per-position next-token statistics of ``logits`` [B, T, V]
    against ``x`` [B, T]: (negative log-likelihood, top-1 hit), each
    [B, T - 1]."""
    logits = logits[:, :-1]
    nxt = x[:, 1:].astype(jnp.int32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, nxt[..., None], axis=-1)[..., 0]
    hit = (jnp.argmax(logits, axis=-1) == nxt).astype(jnp.float32)
    return nll, hit


def exit_log_distribution(z):
    """Log of the exit distribution over the ``R`` passes from the
    gate's pre-activations ``z`` [R, ...]: with ``lambda_t =
    sigmoid(z_t)``, ``q_t = lambda_t prod_{j<t} (1 - lambda_j)`` for
    ``t < R`` and ``q_R = prod_{j<R} (1 - lambda_j)`` (the last
    pass takes what is left; its own gate value is not read)."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(z[:1]), stay], axis=0)
    return before + jnp.concatenate(
        [jax.nn.log_sigmoid(z[:-1]), jnp.zeros_like(z[:1])], axis=0)


def exit_objective(nll, z, beta: float):
    """The looped model's training loss from the per-exit next-token
    losses ``nll`` and gate pre-activations ``z``, both [R, B, T']:
    per position ``sum_t q_t nll_t - beta H(q)``, ``H`` the entropy of
    the exit distribution, mean over positions. Returns (loss,
    {"exit_ce": [R] mean loss of each exit, "exit_mass": [R] mean
    ``q_t``, "exit_entropy": mean ``H(q)``})."""
    log_q = exit_log_distribution(z)
    q = jnp.exp(log_q)
    entropy = -jnp.sum(q * log_q, axis=0)
    loss = jnp.mean(jnp.sum(q * nll, axis=0) - beta * entropy)
    return loss, {"exit_ce": jnp.mean(nll, axis=(1, 2)),
                  "exit_mass": jnp.mean(q, axis=(1, 2)),
                  "exit_entropy": jnp.mean(entropy)}


def exit_stats(params, hs, x, dt):
    """The heads of the passes, one at a time: ``hs`` [R, B, T, D]
    (each pass's normed output) -> (next-token loss, top-1 hit), each
    [R, B, T - 1]. The body is checkpointed, so one exit's float32
    logits live at a time, forward and backward."""
    @jax.checkpoint
    def one_exit(h):
        with jax.named_scope("lm.head"):
            return next_token_stats(_dot(h, params["head"], dt), x)
    return jax.lax.map(one_exit, hs)


def exit_gate(params, hs):
    """The gate's pre-activations ``[R, B, T]``, float32 throughout."""
    gate = params["exit_gate"]
    return jnp.sum(hs * gate["w"], axis=-1) + gate["b"]


# the round's row key <- the part of ``token_loss_parts`` whose mean
# over the round's clients and steps it holds (``exit_mass``: the last
# pass's), in the order the round's metrics carry them
_PART_GAUGES = {
    # a looped model's exit law (``exit_objective``)
    "lm_exit_mass_last": "exit_mass",
    "lm_exit_entropy": "exit_entropy",
    # the expert layers' routing (``ops/routed_experts.py``) and the
    # indexers' ``L_I`` (``ops/sparse_attention.py``), over the layers
    "lm_moe_pairs_local": "moe_pairs",
    "lm_moe_load_max_over_mean": "moe_load_max_over_mean",
    "lm_moe_rows_visited": "moe_rows_visited",
    "lm_index_loss": "index_loss",
    # a biased router's load over ALL routed experts, largest ``|b|``
    # and the balance part's value (zero)
    "lm_router_load_max_over_mean": "router_load_max_over_mean",
    "lm_router_bias_abs_max": "router_bias_abs_max",
    "lm_balance_loss": "balance_loss",
}


def gauge_parts(s: HybridSpec) -> frozenset:
    """The keys of ``token_loss_parts``' parts that feed a gauge, from
    the specification alone: which layers run says which parts come."""
    if s.looped:
        return frozenset({"exit_mass", "exit_entropy"})
    if not s.prenorm:       # the other blocks' layers report no parts
        return frozenset()
    keys = set()
    if s.selection is not None and "full_attention" in s.layer_types:
        keys.add("index_loss")
    if not all(dense_layer(s, i) for i in range(len(s.layer_types))):
        keys |= {"moe_pairs", "moe_load_max_over_mean", "moe_rows_visited"}
        if s.experts.biased:
            keys |= {"router_load_max_over_mean", "router_bias_abs_max",
                     "balance_loss"}
    return frozenset(keys)


class HybridLM(NamedTuple):
    """The model as the engine sees it: :class:`models.common.ModelDef`'s
    surface, with the loss made from ``x`` (``token_loss``) because a
    row's ``y`` is one number and the target is the next token."""
    name: str
    module: HybridSpec          # the evaluation cache's key
    dtype: str
    attention: str
    remat: bool
    eval_batch: int = 1         # rows an evaluation step holds
    is_recurrent: bool = False
    is_regression: bool = False
    has_noise_param: bool = False
    has_aux_loss: bool = False

    @property
    def spec(self) -> HybridSpec:
        return self.module

    @property
    def gauge_names(self) -> Tuple[str, ...]:
        """The row keys of :meth:`round_gauges`, in its order, from the
        specification (``models/common.py``: a token model's gauges);
        empty where the loss reports no such part."""
        parts = gauge_parts(self.module)
        return tuple(name for name, key in _PART_GAUGES.items()
                     if key in parts)

    def round_gauges(self, parts) -> tuple:
        """:attr:`gauge_names`' values, float32 scalars, from
        ``token_loss_parts``' parts stacked over the round's clients
        and steps (``[k, K, ...]``): the means; of the exit masses
        ``[k, K, R]``, the last pass's."""
        keys = gauge_parts(self.module)
        return tuple(
            jnp.mean(parts[key][..., -1] if key == "exit_mass"
                     else parts[key])
            for key in _PART_GAUGES.values() if key in keys)

    def trace_gauges(self, rows: int, tokens: int) -> dict:
        """The row's host floats that are known when a ``rows x
        tokens`` training step is traced here, from the specification,
        the launcher's flags, shapes and the backend
        (``telemetry/schema.py`` describes each key): a looped stack's
        passes; under ``remat`` :func:`kept_counters` of the step as it
        was (or will be) traced; under ``sa_config`` the selected share
        of the causal pairs and whether the layer calls take the fused
        kernels; with latent attention whether they take the flash
        kernel and its backward kernel (each call has the same shapes:
        0 or 1; off a TPU the flash path is its dense oracle)."""
        s, out = self.module, {}
        if s.looped:
            out["ut_steps"] = float(s.total_ut_steps)
        if self.remat:
            out.update(kept_counters(s, tokens, _kept_for(s, rows, tokens),
                                     rows))
        if s.selection is not None:
            out["lm_selected_share"] = sparse_attention.selected_share(
                tokens, s.selection.topk)
            out["lm_selected_kernel_share"] = float(
                sparse_attention.takes_kernel(
                    s.num_attention_heads, s.kv_heads, s.head_size,
                    sparse_attention.chunk_of(tokens, s.selection.chunk),
                    tokens))
        if s.latent is not None:
            from fedtorch_tpu.ops.pallas.flash_attention import (
                backward_kernel_taken,
            )
            flash = resolve_attention(self.attention, tokens) == "flash"
            out["lm_attention_kernel_share"] = float(flash and on_tpu())
            out["lm_attention_backward_kernel_share"] = float(
                flash and backward_kernel_taken(
                    tokens, s.latent.nope + s.latent.rope))
        return out

    def init(self, rng):
        return _jitted_init(self.module)(rng)

    def _states(self, params, x):
        """(compute type, the states the head reads, the layer calls'
        parts: a pre-norm block's, else none)."""
        dt = jnp.dtype(self.dtype)
        states = looped_states if self.module.looped else hidden_states
        h = states(params, x, self.module, dt, self.attention, self.remat)
        return (dt,) + (h if self.module.prenorm else (h, {}))

    def apply(self, params, x, train: bool = False, rng=None, carry=None):
        """Logits ``[B, T, V]``; of the last pass in a looped model."""
        dt, h, _ = self._states(params, x)
        # lint: disable=FTL005 — a looped model or not, by the spec
        if self.module.looped:
            with jax.named_scope("lm.exit"), jax.named_scope("lm.head"):
                return _dot(h[-1], params["head"], dt)
        with jax.named_scope("lm.head"):
            return logits_of(params, h, self.module, dt)

    def token_loss_parts(self, params, x, train: bool = False, rng=None):
        """(loss, top-1, parts) over the B x (T - 1) positions of ``x``
        that have a next token. A single-pass model: the mean
        next-token cross-entropy ``CE``, no parts. A looped model:
        :func:`exit_objective` over its passes, the top-1 of the last
        pass, and the objective's parts. Under ``sa_config``: ``CE +
        L_I``, ``L_I`` the indexers' KL terms
        (``ops/sparse_attention.py``) summed over the layers; the parts
        are ``ce``, ``index_loss`` (``L_I``) and, of an expert layer,
        ``moe_pairs`` and ``moe_load_max_over_mean``
        (``ops/routed_experts.py``), means over the layers. Under a
        biased router (``topk_method`` ``noaux_tc``): ``CE + L_B -
        stop_gradient(L_B)``, ``L_B`` = ``balance_loss_coef`` x the
        layers' ``routed_experts.balance_step`` summed: value zero, its
        gradient the published update of the routers' biases and of
        nothing else; the parts gain ``balance_loss`` (zero: its
        presence says the part ran), ``router_load_max_over_mean``
        (over ALL routed experts, mean over the layers) and
        ``router_bias_abs_max`` (the largest ``|b|`` of any layer)."""
        dt, h, layers = self._states(params, x)
        # lint: disable=FTL005 — a looped model or not, by the spec
        if self.module.looped:
            with jax.named_scope("lm.exit"):
                nll, hit = exit_stats(params, h, x, dt)
                loss, parts = exit_objective(
                    nll, exit_gate(params, h)[..., :-1],
                    self.module.exit_entropy_beta)
                return loss, jnp.mean(hit[-1]), parts
        with jax.named_scope("lm.head"):
            nll, hit = next_token_stats(
                logits_of(params, h, self.module, dt), x)
            loss = jnp.mean(nll)
        # lint: disable=FTL005 — parts or none, by the spec
        if not layers:
            return loss, jnp.mean(hit), {}
        parts = {"ce": loss}
        # lint: disable=FTL005 — sa_config or none, by the spec
        if "index_loss" in layers:
            parts["index_loss"] = jnp.sum(layers["index_loss"])
            loss = loss + parts["index_loss"]
        # lint: disable=FTL005 — experts or none, by the spec
        if "pairs" in layers:
            parts["moe_pairs"] = jnp.mean(layers["pairs"])
            parts["moe_rows_visited"] = jnp.mean(layers["rows_visited"])
            parts["moe_load_max_over_mean"] = jnp.mean(
                layers["load_max_over_mean"])
        # lint: disable=FTL005 — a biased router or none, by the spec
        if "balance" in layers:
            step = self.module.experts.balance * jnp.sum(layers["balance"])
            parts["balance_loss"] = step - jax.lax.stop_gradient(step)
            loss = loss + parts["balance_loss"]
            parts["router_load_max_over_mean"] = jnp.mean(
                layers["router_load_max_over_mean"])
            parts["router_bias_abs_max"] = jnp.max(
                layers["router_bias_abs_max"])
        return loss, jnp.mean(hit), parts

    def token_loss(self, params, x, train: bool = False, rng=None):
        """(loss, top-1) of :meth:`token_loss_parts`."""
        return self.token_loss_parts(params, x, train, rng)[:2]

    def init_carry(self, batch_size: int):
        return None
