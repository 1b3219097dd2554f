"""kanana-2-30b-a3b-instruct-2601 (kakaocorp, config.json, ``model_type``
``deepseek_v3``) in plain float32, as ONE CHIP'S SHARE of a deployment
that divides each layer over several chips by its routed experts:
latent attention without the low-rank query path, one leading dense
layer, then expert layers with a sigmoid router, a per-expert selection
bias, and a shared expert beside the routed ones. It imports nothing of
the program; parameters arrive as the nested dict the launcher's model
initialises, by name.

    loss(params, x, y, cast)    x: [B, T] token ids; y: [B], unused

*A layer* (pre-norm, two RMSNorm scales, eps ``rms_norm_eps``)::

    a = x + Attn(N_1(x))            y = a + FF(N_2(a))

*Attn* (latent attention), ``u = N_1(x)``, H heads::

    q       = W_q u                 H heads of [q_n (nope) | q_r (rope)]
    [c|k_r] = W_a u                 c: kv_lora_rank wide, k_r: rope wide
    c'      = RMSNorm(c)            its own scale (kv_a_layernorm)
    [k_n|v] = W_b c'                H heads of [k_n (nope) | v (v_head_dim)]
    q_r, k_r turned by the rotary embedding; ONE k_r serves all H heads
    k       = [k_n | k_r]
    o_t     = softmax over s <= t of (q_t . k_s / sqrt(nope + rope)) v_s
    Attn    = W_o o                 H x v_head_dim -> hidden

The rotary embedding is the interleaved one (``rope_interleave``): pair
``i`` of the ``rope`` dimensions is elements ``(2i, 2i + 1)``, turned by
``t * theta^(-2i / rope)`` at position ``t``, float32. It is written
here on the pairs where they lie (the published code first moves the
even elements in front of the odd ones and then turns halves: the same
function of the same weights up to one permutation applied to q_r and
k_r alike, which no dot product sees). ``rope_scaling`` is null, so the
softmax scale has no ``mscale`` factor.

*FF of the first ``first_k_dense_replace`` layers*: SwiGLU
``W_d (silu(W_g u) * W_u u)`` of width ``intermediate_size``.

*FF of the other layers*, ``u = N_2(a)``::

    s    = sigmoid(W_r u)                    over all ``routed`` experts,
                                             float32 (never ``cast``: the
                                             published router runs in float32)
    T    = the num_experts_per_tok largest of s + b      (noaux_tc; n_group
                                             = topk_group = 1: no group step)
    g_e  = routed_scaling_factor * s_e / (sum_{e' in T} s_e' + 1e-20)
    FF   = sum over e in T and HELD of g_e E_e(u)  +  S(u)

each ``E_e`` a SwiGLU of width ``moe_intermediate_size`` and ``S`` one
SwiGLU of width ``n_shared_experts x moe_intermediate_size``. **Of the
sum over T the chip computes the terms of the experts it holds**
(``first_expert_held .. + n_routed_experts - 1``), as a ``lax.scan``
over those, each applied to every token and masked by whether the token
chose it; what the absent experts would add is left out, the shared
expert is whole (every chip of the group computes it alike).

*The loss*: ``CE + L_B - stop_gradient(L_B)``. ``CE``: next-token
cross-entropy over the vocabulary's slice, mean over the B x (T - 1)
positions with a next token. ``L_B = -u sum over expert layers sum_e
b_e stop_gradient(sign(mean(c) - c_e))``, ``c_e`` the token-expert
pairs of this step (this chip's B x T tokens, all ``routed`` experts)
that chose ``e`` and ``u`` the file's ``balance_loss_coef``: its value
is subtracted again, its gradient moves ``b`` alone, and plain SGD at
``lr`` then makes DeepSeek-V3's auxiliary-loss-free step ``b_e += lr u
sign(mean(c) - c_e)``. ``b`` receives nothing from ``CE`` (it enters
only through the choice).

Written as a Python loop over the layers, each under
``jax.checkpoint``, attention over blocks of ``BLOCK`` query rows
(``lax.map``, each block under ``jax.checkpoint``), so that at 4096
tokens a block's 32 x 512 x 4096 float32 scores (0.27 GB) live and not
the row's (2.1 GB), beside ``reference/fedavg.py``'s three trees.

Departures from the published code (``modeling_deepseek_v3``), each
listed in the configuration's file under ``assumed`` where it is a
choice:
- the bias update is a loss part and not an optimiser-side step (the
  benchmark's FedAvg reference takes a loss and nothing else); the
  counts are this chip's, where a deployment sums the group's;
- the chip's share: experts held, the vocabulary's slice;
- the rotary pairs turned in place (above); no dropout, no caches.

``cast`` is the control's hook on the two operands of every matrix
product the configuration computes in bfloat16 (projections, ``q k^T``,
``p v``, the experts, the head); the router's product is stated float32
and is not cast. Norms, the rotary turn, softmax, scores, gates and the
loss are pointwise or reductions and stay float32.
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
BLOCK = 512     # query rows a block of attention

_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "kanana2_30b_a3b_l5.json")

KEYS = ("num_hidden_layers", "num_attention_heads", "rms_norm_eps",
        "rope_theta", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")


def load_spec(path: str = _DEFAULT) -> dict:
    """The public config's keys of a configuration file that this model
    reads, with the router's width (``published.n_routed_experts``),
    the first expert held and the balance part's ``u`` beside them."""
    with open(path) as f:
        doc = json.load(f)
    if doc["scoring_func"] != "sigmoid" or not doc["rope_interleave"] \
            or doc["topk_method"] != "noaux_tc" \
            or doc["q_lora_rank"] is not None:
        raise ValueError(
            f"{path}: this reference writes the sigmoid router with "
            "noaux_tc's bias, interleaved rotary pairs and no low-rank "
            "query path, and nothing else")
    spec = {k: doc[k] for k in KEYS}
    spec["routed_experts"] = doc.get("published", {}).get(
        "n_routed_experts", doc["n_routed_experts"])
    spec["first_expert_held"] = doc.get("first_expert_held", 0)
    spec["balance_loss_coef"] = doc.get("balance_loss_coef", 0.0)
    return spec


def dot(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=HI)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def rotary_interleaved(x, theta):
    """``x``: [B, T, H, d]; pair ``i`` = elements ``(2i, 2i + 1)`` of a
    head turned by ``t * theta^(-2i/d)``, in place."""
    T, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention_block(q, k, v, rows, cast):
    """A block of query rows ``q`` [B, C, H, dq] at positions ``rows``
    [C] against ``k`` [B, T, H, dq], ``v`` [B, T, H, dv] -> [B, C, H,
    dv]."""
    s = jnp.einsum("bchd,bshd->bhcs", cast(q), cast(k), precision=HI) \
        / math.sqrt(q.shape[-1])
    causal = jnp.arange(k.shape[1])[None, :] <= rows[:, None]
    prob = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf),
                          axis=-1)
    return jnp.einsum("bhcs,bshd->bchd", cast(prob), cast(v), precision=HI)


def attention(p, u, spec, cast):
    B, T, _ = u.shape
    H, rank = spec["num_attention_heads"], spec["kv_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    theta = spec["rope_theta"]
    q = dot(u, p["wq"], cast).reshape(B, T, H, dn + dr)
    a = dot(u, p["wkv_a"], cast)
    c = rms_norm(a[..., :rank], p["kv_a_norm"], spec["rms_norm_eps"])
    kv = dot(c, p["wkv_b"], cast).reshape(B, T, H, dn + dv)
    k_r = rotary_interleaved(a[..., rank:][:, :, None, :], theta)
    q = jnp.concatenate(
        [q[..., :dn], rotary_interleaved(q[..., dn:], theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, T, H, dr))], axis=-1)
    v = kv[..., dn:]
    C = min(BLOCK, T)
    n = T // C
    assert n * C == T, "rows are whole blocks of query rows"
    block = jax.checkpoint(lambda k, v, xs: attention_block(
        xs[0], k, v, xs[1], cast))
    o = lax.map(lambda xs: block(k, v, xs),
                (jnp.moveaxis(q.reshape(B, n, C, H, dn + dr), 1, 0),
                 jnp.arange(T).reshape(n, C)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * dv)
    return dot(o, p["wo"], cast)


def swiglu(p, u, cast):
    return dot(jax.nn.silu(dot(u, p["gate"], cast)) * dot(u, p["up"], cast),
               p["down"], cast)


def experts(p, u, spec, cast):
    """(the held experts' part of the routed sum plus the shared
    expert, this layer's balance term ``-sum_e b_e sign(mean(c) -
    c_e)``)."""
    routed = spec["routed_experts"]
    s = jax.nn.sigmoid(jnp.matmul(u, p["router"], precision=HI))
    bias = p["router_bias"]
    _, chosen = lax.top_k(s + lax.stop_gradient(bias),
                          spec["num_experts_per_tok"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if spec["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * spec["routed_scaling_factor"]
    held = p["gate"].shape[0]

    def one_expert(out, xs):
        e, w_gate, w_up, w_down = xs
        g = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        y = swiglu({"gate": w_gate, "up": w_up, "down": w_down}, u, cast)
        return out + g[..., None] * y, None

    # one loop body for the experts held (``lax.scan``: unrolled, 16
    # experts a layer compile for minutes), each applied to every token
    # and weighted by the gate of the tokens that chose it
    out, _ = lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(u),
        (spec["first_expert_held"] + jnp.arange(held), p["gate"], p["up"],
         p["down"]))
    load = jnp.sum((chosen.reshape(-1)[:, None]
                    == jnp.arange(routed)[None, :]).astype(jnp.float32),
                   axis=0)
    direction = lax.stop_gradient(jnp.sign(jnp.mean(load) - load))
    return out + swiglu(p["shared"], u, cast), -jnp.sum(bias * direction)


def layer(p, x, dense, spec, cast):
    eps = spec["rms_norm_eps"]
    a = x + attention(p["mixer"], rms_norm(x, p["mixer_norm"], eps), spec,
                      cast)
    u = rms_norm(a, p["mlp_norm"], eps)
    if dense:
        return a + swiglu(p["mlp"], u, cast), 0.0
    o, balance = experts(p["mlp"], u, spec, cast)
    return a + o, balance


def cross_entropy(params, h, x, spec, cast):
    h = rms_norm(h, params["final_norm"], spec["rms_norm_eps"])
    logits = dot(h, params["head"], cast)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nxt = x[:, 1:, None].astype(jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, nxt, axis=-1))


def objective(params, x, spec, cast=_ops.identity):
    """(loss, CE, L_B): the loss is ``CE + L_B - stop_gradient(L_B)``."""
    h, balance = params["embed"][x], 0.0
    for i in range(spec["num_hidden_layers"]):
        dense = i < spec["first_k_dense_replace"]
        h, term = jax.checkpoint(
            lambda p, h, dense=dense: layer(p, h, dense, spec, cast))(
            params[f"layer_{i}"], h)
        balance = balance + term
    ce = jax.checkpoint(lambda p, h: cross_entropy(p, h, x, spec, cast))(
        {"final_norm": params["final_norm"], "head": params["head"]}, h)
    balance = spec["balance_loss_coef"] * balance
    return ce + balance - lax.stop_gradient(balance), ce, balance


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
