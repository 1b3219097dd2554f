"""Ouro (ByteDance/Ouro-2.6B, config.json) as a looped causal language
model in plain float32: one stack of full-attention layers run
``total_ut_steps`` times with the same weights, a head and an exit gate
after every pass. It imports nothing of the program; parameters arrive
as the nested dict the launcher's model initialises, by name.

    loss(params, x, y, cast)    x: [B, T] token ids; y: [B], unused

*The loop.* ``h_0 = E[x]``; for pass ``t = 1..R``::

    h_t      = N_f(L_n(... L_1(h_{t-1})))      same layers, same N_f
    logits_t = W_head h_t
    lambda_t = sigmoid(w_g . h_t + b_g)        one gate for all passes

*A layer* (sandwich norms: four RMSNorm scales a layer)::

    a = x + N_2(Attn(N_1(x)))        y = a + N_4(MLP(N_3(a)))
    Attn(u): q, k, v = W_q u, W_k u, W_v u in ``num_attention_heads``
             heads; rotary embedding on q and k (rotate-half: element i
             of a head pairs with element i + d/2, positions 0..T-1,
             angle position * theta^(-2i/d)); causal softmax of
             q k^T / sqrt(d); W_o.  No QK-norm, no biases.
    MLP(u) = W_d (silu(W_g u) * W_u u)

*The loss* (Zhu et al. 2025, "Scaling Latent Reasoning via Looped
Language Models", stage I). Exit distribution ``q_1 = lambda_1``,
``q_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < R``,
``q_R = prod_{j<R} (1 - lambda_j)``; per position
``sum_t q_t CE_t - beta H(q)`` with ``CE_t`` the next-token
cross-entropy of ``logits_t`` and ``H(q) = -sum_t q_t log q_t``; mean
over the ``B * (T - 1)`` positions that have a next token.

Written as a Python loop over the layers and the exits, and a
``lax.scan`` over the passes. Each layer call and each exit's
cross-entropy runs under ``jax.checkpoint``, so that the float32
activations of ``R * n`` layer calls fit beside ``reference/fedavg.py``'s
three trees; the softmax is written whole (16 heads of 2048 x 2048
float32 scores are 0.27 GB a layer call). The passes were a Python loop
first: the local step's executable then held ``R * n`` = 32 layer
bodies, forward and backward, 293 MB, more than the chip machine's
compile cache takes (192 MiB), and compiled for 155 s in every run (my
chip runs, PR 35); scanned, the program holds ``n`` bodies. The loop is
tested against a two-pass case unrolled by hand
(``benchmark/tests/test_ouro_reference.py``).

Departures from the published description, each because config.json
does not say (the configuration's file lists them under ``assumed``):
- the norm placement (sandwich) and that ``N_f`` closes every pass and
  feeds the next, as the published modelling code has it;
- the gate's form (one ``hidden_size -> 1`` linear layer with a bias on
  the normed ``h_t``) and that the passes share it;
- ``beta`` (``exit_entropy_beta`` in the file, 0.05) and that local
  training uses the stage-I objective;
- ``q`` is formed in logarithms (``log_sigmoid``), which is the same
  number and does not underflow.

``cast`` is the control's hook on the two operands of every matrix
product (projections, scores, the weighted values, the MLP, the head).
Norms, the rotary turn, softmax, the gate (a 2048-term sum the program
keeps in float32 as well) and the loss are pointwise or reductions and
stay float32.
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
    os.path.abspath(__file__))), "configs", "ouro_2_6b_l8.json")


def load_spec(path: str = _DEFAULT) -> dict:
    """The public config's keys of a configuration file that this model
    reads."""
    with open(path) as f:
        doc = json.load(f)
    return {"num_hidden_layers": doc["num_hidden_layers"],
            "num_attention_heads": doc["num_attention_heads"],
            "rms_norm_eps": doc["rms_norm_eps"],
            "rope_theta": doc.get("rope_theta"),
            "total_ut_steps": doc.get("total_ut_steps", 1),
            "exit_entropy_beta": doc.get("exit_entropy_beta", 0.05)}


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


def attention(p, u, spec, cast):
    B, T, D = u.shape
    H = spec["num_attention_heads"]
    hd = D // H
    q, k, v = (dot(u, p[n], cast).reshape(B, T, H, hd)
               for n in ("wq", "wk", "wv"))
    if spec["rope_theta"] is not None:
        q, k = rotary(q, spec["rope_theta"]), rotary(k, spec["rope_theta"])
    s = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k), precision=HI) \
        / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    prob = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", cast(prob), cast(v), precision=HI)
    return dot(out.reshape(B, T, D), p["wo"], cast)


def mlp(p, u, cast):
    return dot(jax.nn.silu(dot(u, p["gate"], cast)) * dot(u, p["up"], cast),
               p["down"], cast)


def layer(p, x, spec, cast):
    eps = spec["rms_norm_eps"]
    a = x + rms_norm(attention(p["mixer"],
                               rms_norm(x, p["mixer_in_norm"], eps),
                               spec, cast), p["mixer_norm"], eps)
    return a + rms_norm(mlp(p["mlp"], rms_norm(a, p["mlp_in_norm"], eps),
                            cast), p["mlp_norm"], eps)


def passes(params, x, spec, cast=_ops.identity):
    """``x``: [B, T] token ids -> the list of ``h_t`` [B, T, D], one a
    pass, each after the final norm."""
    def one_pass(h, _):
        for i in range(spec["num_hidden_layers"]):
            h = jax.checkpoint(lambda p, h: layer(p, h, spec, cast))(
                params[f"layer_{i}"], h)
        h = rms_norm(h, params["final_norm"], spec["rms_norm_eps"])
        return h, h

    _, hs = lax.scan(one_pass, params["embed"][x], None,
                     length=spec["total_ut_steps"])
    return [hs[t] for t in range(spec["total_ut_steps"])]


def exit_cross_entropy(head, h, x, cast):
    """Per-position next-token cross-entropy ``[B, T - 1]`` of one
    pass's logits."""
    logits = dot(h, head, cast)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nxt = x[:, 1:, None].astype(jnp.int32)
    return -jnp.take_along_axis(logp, nxt, axis=-1)[..., 0]


def exit_log_q(z):
    """``z``: the list of gate pre-activations, one a pass -> the list
    of ``log q_t``."""
    out, stay = [], jnp.zeros_like(z[0])
    for z_t in z[:-1]:
        out.append(stay + jax.nn.log_sigmoid(z_t))       # stop here
        stay = stay + jax.nn.log_sigmoid(-z_t)           # or go on
    return out + [stay]


def objective(params, x, spec, cast=_ops.identity):
    """(loss, per-exit mean cross-entropies, mean exit masses)."""
    hs = passes(params, x, spec, cast)
    ce = [jax.checkpoint(lambda w, h: exit_cross_entropy(w, h, x, cast))(
        params["head"], h) for h in hs]
    gate = params["exit_gate"]
    z = [(jnp.sum(h * gate["w"], axis=-1) + gate["b"])[:, :-1]
         for h in hs]
    log_q = exit_log_q(z)
    q = [jnp.exp(l) for l in log_q]
    expected = sum(q_t * ce_t for q_t, ce_t in zip(q, ce))
    entropy = -sum(q_t * l for q_t, l in zip(q, log_q))
    loss = jnp.mean(expected - spec["exit_entropy_beta"] * entropy)
    return loss, [jnp.mean(c) for c in ce], [jnp.mean(q_t) for q_t in q]


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
