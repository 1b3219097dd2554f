"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, config.json) in
plain float32, as ONE CHIP'S SHARE of a deployment that divides each
layer over several chips by its experts: a Qwen3-MoE block (grouped
query heads, an RMSNorm over each head of q and k, sparse experts)
whose attention reads a learned selection of keys (DeepSeek-V3.2-Exp's
sparse attention, which the config's ``sa_config`` sizes). It imports
nothing of the program; parameters arrive as the nested dict the
launcher's model initialises, by name.

    loss(params, x, y, cast)    x: [B, T] token ids; y: [B], unused

*A layer* (pre-norm, two RMSNorm scales)::

    a = x + Attn(N_1(x))            y = a + MoE(N_2(a))

*Attn*, ``u = N_1(x)``: ``q = W_q u`` in H heads of ``head_dim``,
``k, v = W_k u, W_v u`` in KV heads, key head ``j`` serving query heads
``j H/KV .. (j + 1) H/KV - 1``; RMSNorm over each head of q and of k
(one scale each, shared by the heads); rotary embedding on q and k
(rotate-half: element i of a head pairs with element i + d/2,
positions 0..T-1, angle position * theta^(-2i/d); for text the three
``mrope`` position streams are the same 0..T-1, so the sections fall
together into this ordinary embedding);
``o_t = softmax over s in S_t of (q_t . k_s / sqrt(head_dim)) v_s``;
``W_o``. No biases.

*The indexer* (one key head)::

    qI_{t,j} = W_Iq^j u_t  (j = 1..J),   kI_s = W_Ik u_s,   w_t = W_Iw u_t
    I_{t,s}  = sum_j w_{t,j} relu(qI_{t,j} . kI_s)          s <= t
    S_t      = the topk positions s <= t of largest I_{t,s}
               (every s <= t while t < topk)

``lax.top_k`` of each query's row of scores, the later positions at
``-inf``: its last value is the bar a position's score has to reach
(scores equal to the bar all pass).

*MoE*, ``u = N_2(a)``: ``r = softmax(W_r u)`` over all ``routed``
experts, ``T`` the ``num_experts_per_tok`` largest,
``g_e = r_e / sum_{e' in T} r_e'`` (``norm_topk_prob``), and **of the
sum over ``T`` the chip computes the terms of the experts it holds**
(``first_expert_held .. + num_experts - 1``)::

    MoE(u) = sum over e in T and held of g_e W_d^e (silu(W_g^e u) * W_u^e u)

a loop (``lax.scan``) over the experts held, each applied to every
token and masked by whether the token chose it: no dispatch, no
grouped product. What
the absent experts would add is left out, as in the program.

*The loss*: ``CE + L_I``. ``CE``: next-token cross-entropy over the
vocabulary's slice, mean over the B x (T - 1) positions with a next
token. ``L_I = sum over layers of mean over the B x T queries of
KL(p_t || softmax over S_t of I_{t,.})``, ``p_t`` the attention's
probabilities over ``S_t`` summed over the H heads and divided by H,
under ``stop_gradient``; the indexer reads ``stop_gradient(u)``. So
``L_I``'s gradient reaches the indexer's three matrices alone and
``CE``'s every other leaf.

Written as a Python loop over the layers, each under
``jax.checkpoint``, and over blocks of ``q_chunk_size`` query rows
(``lax.map``, each block under ``jax.checkpoint``), so that at 8192
tokens a block's 32 x 512 x 8192 float32 scores (0.54 GB) live and not
the row's (8.6 GB), beside ``reference/fedavg.py``'s three trees.

Departures from the published description, each because config.json
does not say (the configuration's file lists them under ``assumed``):
- the pre-norm block and the per-head QK-norm of the family whose keys
  the config carries (Qwen3-MoE);
- the indexer reads the layer's normed input, takes the rotary
  embedding over its own 64 dimensions (same theta), has no norm of its
  own, and ``w`` is scaled by ``(J x indexer_head_dim) ** -0.5``;
- the objective of local training: DeepSeek-V3.2-Exp's sparse training
  stage, ``CE + L_I`` with coefficient 1, the layers' terms summed;
- no router auxiliary loss (the config names none);
- the vision tower is left out (text rows only).

``cast`` is the control's hook on the two operands of every matrix
product (projections, the indexer's scores, ``q k^T``, ``p v``, the
router, the experts, the head). Norms, the rotary turn, softmax, the
weighted sum over the indexer's heads, gates and both loss parts are
pointwise or reductions and stay float32.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from . import _ops

HI = lax.Precision.HIGHEST

_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "keye_vl2_30b_a3b_l4.json")


def load_spec(path: str = _DEFAULT) -> dict:
    """The public config's keys of a configuration file that this model
    reads, with the router's width (``published.num_experts``) and the
    first expert held beside them."""
    with open(path) as f:
        doc = json.load(f)
    sa = doc["sa_config"]
    return {"num_hidden_layers": doc["num_hidden_layers"],
            "num_attention_heads": doc["num_attention_heads"],
            "num_key_value_heads": doc["num_key_value_heads"],
            "head_dim": doc["head_dim"],
            "rms_norm_eps": doc["rms_norm_eps"],
            "rope_theta": doc["rope_theta"],
            "num_experts_per_tok": doc["num_experts_per_tok"],
            "norm_topk_prob": doc["norm_topk_prob"],
            "routed_experts": doc.get("published", {}).get(
                "num_experts", doc["num_experts"]),
            "first_expert_held": doc.get("first_expert_held", 0),
            "indexer_num_heads": sa["indexer_num_heads"],
            "indexer_head_dim": sa["indexer_head_dim"],
            "topk": sa["topk"], "q_chunk_size": sa["q_chunk_size"]}


def dot(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=HI)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def rotary(x, theta):
    """``x``: [B, T, H, d], each head turned by its position's angles."""
    T, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def selection(scores, rows, topk):
    """The mask [B, C, T] of the ``topk`` largest of each query's
    ``scores`` [B, C, T] among the positions up to its own (``rows``
    [C]); every such position where there are no more than ``topk``."""
    T = scores.shape[-1]
    causal = jnp.arange(T)[None, :] <= rows[:, None]
    if T <= topk:
        return jnp.broadcast_to(causal[None], scores.shape)
    masked = jnp.where(causal[None], scores, -jnp.inf)
    best, _ = lax.top_k(masked, topk)
    # the topk-th largest is the bar (-inf for a query with fewer
    # positions up to its own: every one of them passes)
    return causal[None] & (masked >= best[..., -1:])


def attention_block(q, k, v, qi, ki, wi, rows, spec, cast):
    """A block of query rows: ``q`` [B, C, H, d], ``k`` / ``v``
    [B, T, KV, d], the indexer's ``qi`` [B, C, J, di], ``ki``
    [B, T, di], ``wi`` [B, C, J] -> (o [B, C, H, d], the KL term of
    each query [B, C])."""
    B, C, H, d = q.shape
    group = H // k.shape[2]
    index = jnp.einsum("bcjd,bsd->bcjs", cast(qi), cast(ki), precision=HI)
    index = jnp.sum(jax.nn.relu(index) * wi[..., None], axis=2)
    chosen = selection(index, rows, spec["topk"])
    kk, vv = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bchd,bshd->bhcs", cast(q), cast(kk), precision=HI) \
        / math.sqrt(d)
    prob = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhcs,bshd->bchd", cast(prob), cast(vv), precision=HI)
    target = lax.stop_gradient(jnp.sum(prob, axis=1) / H)
    log_q = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), axis=-1)
    terms = target * (jnp.log(jnp.where(target > 0, target, 1.0))
                      - jnp.where(chosen, log_q, 0.0))
    return o, jnp.sum(jnp.where(chosen, terms, 0.0), axis=-1)


def attention(p, u, spec, cast):
    """(Attn(u) [B, T, D], this layer's KL term: its mean over the
    B x T queries)."""
    B, T, _ = u.shape
    H, KV, d = (spec["num_attention_heads"], spec["num_key_value_heads"],
                spec["head_dim"])
    J, di = spec["indexer_num_heads"], spec["indexer_head_dim"]
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    q = rms_norm(dot(u, p["wq"], cast).reshape(B, T, H, d), p["q_norm"], eps)
    k = rms_norm(dot(u, p["wk"], cast).reshape(B, T, KV, d), p["k_norm"],
                 eps)
    v = dot(u, p["wv"], cast).reshape(B, T, KV, d)
    q, k = rotary(q, theta), rotary(k, theta)
    ui = lax.stop_gradient(u)
    qi = rotary(dot(ui, p["index_q"], cast).reshape(B, T, J, di), theta)
    ki = rotary(dot(ui, p["index_k"], cast).reshape(B, T, 1, di),
                theta)[:, :, 0]
    wi = dot(ui, p["index_w"], cast) * (J * di) ** -0.5
    C = min(spec["q_chunk_size"], T)
    n = T // C
    assert n * C == T, "rows are whole blocks of q_chunk_size"
    blocks = lambda t: jnp.moveaxis(
        t.reshape((B, n, C) + t.shape[2:]), 1, 0)
    block = jax.checkpoint(lambda k, v, ki, xs: attention_block(
        xs[0], k, v, xs[1], ki, xs[2], xs[3], spec, cast))
    o, kl = lax.map(lambda xs: block(k, v, ki, xs),
                    (blocks(q), blocks(qi), blocks(wi),
                     jnp.arange(T).reshape(n, C)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * d)
    return dot(o, p["wo"], cast), jnp.mean(kl)


def experts(p, u, spec, cast):
    """The held experts' part of MoE(u)."""
    r = jax.nn.softmax(dot(u, p["router"], cast), axis=-1)
    top, chosen = lax.top_k(r, spec["num_experts_per_tok"])
    if spec["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = p["gate"].shape[0]

    def one_expert(out, xs):
        e, w_gate, w_up, w_down = xs
        g = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        y = dot(jax.nn.silu(dot(u, w_gate, cast)) * dot(u, w_up, cast),
                w_down, cast)
        return out + g[..., None] * y, None

    # one loop body for the experts held (``lax.scan``: the program of
    # 16 unrolled experts a layer compiled for minutes), each applied to
    # every token and weighted by the gate of the tokens that chose it
    out, _ = lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(u),
        (spec["first_expert_held"] + jnp.arange(held), p["gate"], p["up"],
         p["down"]))
    return out


def layer(p, x, spec, cast):
    eps = spec["rms_norm_eps"]
    o, kl = attention(p["mixer"], rms_norm(x, p["mixer_norm"], eps), spec,
                      cast)
    a = x + o
    return a + experts(p["mlp"], rms_norm(a, p["mlp_norm"], eps), spec,
                       cast), kl


def cross_entropy(params, h, x, spec, cast):
    h = rms_norm(h, params["final_norm"], spec["rms_norm_eps"])
    logits = dot(h, params["head"], cast)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nxt = x[:, 1:, None].astype(jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, nxt, axis=-1))


def objective(params, x, spec, cast=_ops.identity):
    """(loss, CE, L_I)."""
    h, index_loss = params["embed"][x], 0.0
    for i in range(spec["num_hidden_layers"]):
        h, kl = jax.checkpoint(lambda p, h: layer(p, h, spec, cast))(
            params[f"layer_{i}"], h)
        index_loss = index_loss + kl
    ce = jax.checkpoint(lambda p, h: cross_entropy(p, h, x, spec, cast))(
        {"final_norm": params["final_norm"], "head": params["head"]}, h)
    return ce + index_loss, ce, index_loss


def make_loss(spec: dict):
    def loss(params, x, y, cast=_ops.identity):
        del y      # a row's label; the target is the next token
        return objective(params, x, spec, cast)[0]
    return loss


_SPEC = None


def loss(params, x, y, cast=_ops.identity):
    """The loss at the configuration's own specification."""
    global _SPEC
    if _SPEC is None:
        _SPEC = load_spec()
    return make_loss(_SPEC)(params, x, y, cast)
