"""Olmo-Hybrid (allenai/Olmo-Hybrid-7B, config.json) as a causal
language model in plain float32: gated delta-rule linear attention
beside full softmax attention by ``layer_types``, SwiGLU in every
layer, untied head, no biases. It imports nothing of the program;
parameters arrive as the nested dict the launcher's model
initialises, by name.

    loss(params, x, y, cast)    x: [B, T] token ids; y: [B], unused

The loss is the mean next-token cross-entropy over the ``B * (T - 1)``
positions that have a next token; the target is made from ``x``.

*Linear-attention layer* (Yang et al., Gated Delta Networks,
arXiv:2412.06464, with the negative eigenvalues ``linear_allow_neg_eigval``
admits). With ``conv4`` a causal depthwise convolution of
``linear_conv_kernel_dim`` taps over time::

    q = silu(conv4(W_q x))   k = silu(conv4(W_k x))   v = silu(conv4(W_v x))
    per head:  q <- q / |q| / sqrt(d_k)     k <- k / |k|
    beta_t  = 2 sigmoid(W_b x_t)
    alpha_t = exp(-exp(A_log) softplus(W_a x_t + dt_bias))
    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    y   = W_o (rmsnorm_head(o) * silu(W_g x))

computed here in its **recurrent form**, token by token: ``lax.scan``
over the tokens of a block inside ``jax.checkpoint``, blocks scanned in
turn, so that the backward pass holds one state a block and one block's
states, never all ``T``.

*Full-attention layer*: ``num_attention_heads`` heads, as many key and
value heads, causal softmax written out, a block of query rows at a
time so that the float32 scores fit.

Departures from the published description, each because config.json
does not say (the configuration's file lists them under ``assumed``):
- norm placement as the Olmo 2/3 family has it: RMSNorm on each
  sublayer's OUTPUT before the residual add, no norm on its input;
- RMSNorm over the whole projected q and k of a full-attention layer;
- no rotary embedding in the full layers (``rope_theta: null``);
- the delta-rule layer's output norm is per head over ``d_v`` with one
  learned scale shared by the heads, applied before the gate;
- the l2 normalization of q and k adds 1e-6 under the root.

``cast`` is the control's hook on the two operands of every matrix
product (the state's products of the recurrence among them); the
depthwise convolution, norms, gates and softmax are pointwise or
reductions and stay float32.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from . import _ops

TOKEN_BLOCK = 64      # tokens a checkpointed block of the recurrence
QUERY_BLOCK = 512     # query rows a checkpointed block of softmax
HI = lax.Precision.HIGHEST

_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "olmo_hybrid_7b_l4.json")


def load_spec(path: str = _DEFAULT) -> dict:
    """The public config's keys of a configuration file, the layer
    kinds cut to ``num_hidden_layers``."""
    with open(path) as f:
        doc = json.load(f)
    spec = {k: doc[k] for k in (
        "hidden_size", "num_attention_heads", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "rms_norm_eps")}
    spec["layer_types"] = doc["layer_types"][:doc["num_hidden_layers"]]
    return spec


def dot(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=HI)


def cast_nonzero(cast, x):
    """``cast(x)``, or ``x`` itself where it is all zeros (the state
    before the first token, a padded token): a rounding leaves zeros
    as they are, and the float8 control scales by 448 / max|x|, whose
    gradient is not finite there."""
    if cast is _ops.identity:
        return x
    return lax.cond(jnp.any(x != 0), cast, _ops.identity, x)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def l2_normalize(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + 1e-6)


def causal_conv(x, w):
    """Depthwise over time: ``y_t = sum_i w[:, i] x_{t - (taps-1) + i}``.
    ``x``: [B, T, C]; ``w``: [C, taps]."""
    taps = w.shape[1]
    pad = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    T = x.shape[1]
    return sum(pad[:, i:i + T] * w[:, i] for i in range(taps))


def recurrent_delta_rule(q, k, v, g, beta, cast=_ops.identity,
                         block: int = TOKEN_BLOCK):
    """The gated delta rule token by token. ``q``, ``k``: [B, T, H, dk];
    ``v``: [B, T, H, dv]; ``g`` (log decay), ``beta``: [B, T, H].
    Returns ``o``: [B, T, H, dv]. Padded to whole blocks with tokens
    that leave the state as it is."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    pad = (-T) % block
    if pad:
        widen = lambda x: jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = (widen(x) for x in (q, k, v, g, beta))
    n = (T + pad) // block
    # time first, in blocks: [n, block, B, H, ...]
    blocks = lambda x: jnp.moveaxis(x, 1, 0).reshape(
        (n, block) + x.shape[:1] + x.shape[2:])

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs            # [B, H, d], [B, H]
        S = S * jnp.exp(g_t)[..., None, None]
        # what the decayed state holds for this key: S^T k
        k_c = cast_nonzero(cast, k_t)
        held = jnp.einsum("bhde,bhd->bhe", cast_nonzero(cast, S), k_c,
                          precision=HI)
        u = b_t[..., None] * (v_t - held)
        S = S + jnp.einsum("bhd,bhe->bhde", k_c, cast_nonzero(cast, u),
                           precision=HI)
        o = jnp.einsum("bhde,bhd->bhe", cast_nonzero(cast, S),
                       cast_nonzero(cast, q_t), precision=HI)
        return S, o

    @jax.checkpoint
    def one_block(S, xs):
        return lax.scan(token, S, xs)

    _, o = lax.scan(one_block, jnp.zeros((B, H, dk, dv), jnp.float32),
                    tuple(blocks(x) for x in (q, k, v, g, beta)))
    o = o.reshape((n * block, B, H, dv))
    return jnp.moveaxis(o, 0, 1)[:, :T]


def linear_attention(p, x, spec, cast):
    B, T, _ = x.shape
    H = spec["linear_num_key_heads"]
    assert spec["linear_num_value_heads"] == H, \
        "grouped value heads are not written out here"
    dk, dv = spec["linear_key_head_dim"], spec["linear_value_head_dim"]
    qkv = jnp.concatenate([dot(x, p[n], cast) for n in ("wq", "wk", "wv")],
                          axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, p["conv"]))
    q, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
    q = l2_normalize(q.reshape(B, T, H, dk)) / math.sqrt(dk)
    k = l2_normalize(k.reshape(B, T, H, dk))
    v = v.reshape(B, T, H, dv)
    beta = 2.0 * jax.nn.sigmoid(dot(x, p["wb"], cast))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
        dot(x, p["wa"], cast) + p["dt_bias"])
    o = recurrent_delta_rule(q, k, v, g, beta, cast)
    o = rms_norm(o, p["o_norm"], spec["rms_norm_eps"])
    gate = jax.nn.silu(dot(x, p["wg"], cast)).reshape(B, T, H, dv)
    return dot((o * gate).reshape(B, T, H * dv), p["wo"], cast)


def full_attention(p, x, spec, cast):
    B, T, D = x.shape
    H = spec["num_attention_heads"]
    hd = p["wq"].shape[1] // H
    eps = spec["rms_norm_eps"]
    q = rms_norm(dot(x, p["wq"], cast), p["q_norm"], eps)
    k = rms_norm(dot(x, p["wk"], cast), p["k_norm"], eps)
    v = dot(x, p["wv"], cast)
    q, k, v = (t.reshape(B, T, H, hd) for t in (q, k, v))
    rows = min(QUERY_BLOCK, T)
    pad = (-T) % rows
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = (T + pad) // rows
    kc, vc = cast(k), cast(v)

    @jax.checkpoint
    def block(args):
        i, q_i = args                            # q_i: [B, rows, H, hd]
        s = jnp.einsum("bqhd,bkhd->bhqk", cast(q_i), kc, precision=HI) \
            / math.sqrt(hd)
        q_pos = i * rows + jnp.arange(rows)
        s = jnp.where(q_pos[:, None] >= jnp.arange(T)[None, :], s,
                      -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", cast(prob), vc,
                          precision=HI)

    q_blocks = jnp.moveaxis(qp.reshape(B, n, rows, H, hd), 1, 0)
    out = lax.map(block, (jnp.arange(n), q_blocks))
    out = jnp.moveaxis(out, 0, 1).reshape(B, n * rows, H * hd)[:, :T]
    return dot(out, p["wo"], cast)


def mlp(p, x, cast):
    return dot(jax.nn.silu(dot(x, p["gate"], cast)) * dot(x, p["up"], cast),
               p["down"], cast)


def layer(p, x, kind, spec, cast):
    mixer = linear_attention if kind == "linear_attention" \
        else full_attention
    eps = spec["rms_norm_eps"]
    x = x + rms_norm(mixer(p["mixer"], x, spec, cast), p["mixer_norm"],
                     eps)
    return x + rms_norm(mlp(p["mlp"], x, cast), p["mlp_norm"], eps)


def forward(params, x, spec, cast=_ops.identity):
    """``x``: [B, T] token ids -> logits [B, T, vocab]."""
    h = params["embed"][x]
    for i, kind in enumerate(spec["layer_types"]):
        h = jax.checkpoint(
            lambda p, h, kind=kind: layer(p, h, kind, spec, cast))(
            params[f"layer_{i}"], h)
    h = rms_norm(h, params["final_norm"], spec["rms_norm_eps"])
    return dot(h, params["head"], cast)


def make_loss(spec: dict):
    def loss(params, x, y, cast=_ops.identity):
        del y      # a row's label; the target is the next token
        logits = forward(params, x, spec, cast)[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nxt = x[:, 1:, None].astype(jnp.int32)
        return -jnp.mean(jnp.take_along_axis(logp, nxt, axis=-1))
    return loss


_SPEC = None


def loss(params, x, y, cast=_ops.identity):
    """The loss at the configuration's own specification."""
    global _SPEC
    if _SPEC is None:
        _SPEC = load_spec()
    return make_loss(_SPEC)(params, x, y, cast)
