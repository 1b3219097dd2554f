"""A causal language model whose shape is read from a file: the keys
of a public ``config.json`` (``hidden_size``, ``intermediate_size``,
``num_hidden_layers``, ``layer_types``, ``num_attention_heads``,
``linear_*``, ``vocab_size``, ``rms_norm_eps``), as the Olmo-Hybrid
family states them. ``layer_types`` names each layer's mixer:

* ``linear_attention`` — the gated delta rule (``ops/delta_rule.py``)
  behind a causal depthwise convolution, with an output gate;
* ``full_attention`` — causal softmax attention through
  ``ops/attention_dispatch.py`` (flash from 4096 tokens on).

Every layer ends in a SwiGLU MLP; norms sit on each sublayer's OUTPUT
before the residual add (the Olmo 2/3 placement), the head is untied,
nothing has a bias. ``benchmark/reference/olmo_hybrid.py`` writes the
same equations out in plain float32 and lists what the public config
leaves open.

Pure functions over a nested dict of float32 parameters; every layer
is a subtree of its own (``layer_<i>``: no stacked scan, so a
gradient is consumed leaf by leaf). Products take bfloat16 operands
where the launcher's ``compute_dtype`` says so and accumulate in
float32; the residual stream, norms, softmax, decays, the convolution
and the loss are float32. With ``remat`` each layer runs under ``jax.checkpoint``.

Scopes for the device trace: ``lm.delta_rule``, ``lm.attention``,
``lm.mlp``, ``lm.head``.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from fedtorch_tpu.ops.attention_dispatch import resolve_attention
from fedtorch_tpu.ops.delta_rule import chunk_gated_delta_rule

LAYER_KINDS = ("linear_attention", "full_attention")
INIT_STD = 0.02


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


def load_spec(path: str) -> HybridSpec:
    """Read a specification file. Keys beyond the public config's are
    ignored (a benchmark configuration's file carries its launcher
    flags beside them); ``layer_types`` is cut to
    ``num_hidden_layers``."""
    with open(path) as f:
        doc = json.load(f)
    missing = [k for k in HybridSpec._fields + ("num_hidden_layers",)
               if k not in doc]
    if missing:
        raise ValueError(f"model specification {path!r} lacks {missing}")
    kinds = tuple(doc["layer_types"][:int(doc["num_hidden_layers"])])
    if len(kinds) != int(doc["num_hidden_layers"]) \
            or any(k not in LAYER_KINDS for k in kinds):
        raise ValueError(
            f"model specification {path!r}: layer_types must name "
            f"num_hidden_layers layers, each one of {LAYER_KINDS}")
    if doc.get("num_key_value_heads", doc["num_attention_heads"]) \
            != doc["num_attention_heads"] \
            or doc["linear_num_value_heads"] != doc["linear_num_key_heads"]:
        raise ValueError(
            f"model specification {path!r}: grouped key/value heads are "
            "not supported (as many key and value heads as query heads)")
    if doc.get("tie_word_embeddings") or doc.get("attention_bias"):
        raise ValueError(
            f"model specification {path!r}: tied embeddings and "
            "attention biases are not supported")
    return HybridSpec(**{k: (kinds if k == "layer_types" else doc[k])
                         for k in HybridSpec._fields})


def _linear_shapes(s: HybridSpec) -> dict:
    d, h = s.hidden_size, s.linear_num_key_heads
    qk, vv = h * s.linear_key_head_dim, h * s.linear_value_head_dim
    return {"wq": (d, qk), "wk": (d, qk), "wv": (d, vv), "wg": (d, vv),
            "wo": (vv, d), "wa": (d, h), "wb": (d, h),
            "conv": (2 * qk + vv, s.linear_conv_kernel_dim),
            "a_log": (h,), "dt_bias": (h,),
            "o_norm": (s.linear_value_head_dim,)}


def _full_shapes(s: HybridSpec) -> dict:
    d = s.hidden_size
    return {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "q_norm": (d,), "k_norm": (d,)}


def param_shapes(s: HybridSpec) -> dict:
    d, f = s.hidden_size, s.intermediate_size
    tree = {"embed": (s.vocab_size, d), "final_norm": (d,),
            "head": (d, s.vocab_size)}
    for i, kind in enumerate(s.layer_types):
        tree[f"layer_{i}"] = {
            "mixer": _linear_shapes(s) if kind == "linear_attention"
            else _full_shapes(s),
            "mixer_norm": (d,), "mlp_norm": (d,),
            "mlp": {"gate": (d, f), "up": (d, f), "down": (f, d)}}
    return tree


def init_params(spec: HybridSpec, rng) -> Any:
    """Seeded float32 parameters: matrices normal(0, 0.02), norm
    scales 1, the convolution uniform(+-1/sqrt(taps)), decay rates
    ``exp(a_log)`` spread over 1..16 and time steps
    ``softplus(dt_bias)`` log-spread over 0.001..0.1 across the heads
    (the delta-rule family's own initialisation)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        key = jax.random.fold_in(rng, i)
        if name.endswith("norm"):
            leaf = jnp.ones(shape, jnp.float32)
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
            leaf = INIT_STD * jax.random.normal(key, shape, jnp.float32)
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


def _dot(x, w, dt):
    """``x @ w``: operands in the compute dtype, float32 out."""
    return jnp.matmul(x.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32)


def _linear_attention(p, x, s: HybridSpec, dt):
    B, T, _ = x.shape
    h, dk, dv = (s.linear_num_key_heads, s.linear_key_head_dim,
                 s.linear_value_head_dim)
    qkv = jnp.concatenate([_dot(x, p[n], dt) for n in ("wq", "wk", "wv")],
                          axis=-1)
    qkv = jax.nn.silu(_causal_conv(qkv, p["conv"]))
    q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
    q = _l2_normalize(q.reshape(B, T, h, dk)) / math.sqrt(dk)
    k = _l2_normalize(k.reshape(B, T, h, dk))
    v = v.reshape(B, T, h, dv)
    # the factor 2 is the negative eigenvalue the config allows
    beta = 2.0 * jax.nn.sigmoid(_dot(x, p["wb"], dt))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
        _dot(x, p["wa"], dt) + p["dt_bias"])
    with jax.named_scope("lm.delta_rule"):
        o = chunk_gated_delta_rule(q.astype(dt), k.astype(dt),
                                   v.astype(dt), g, beta)
    o = _rms_norm(o, p["o_norm"], s.rms_norm_eps)
    gate = jax.nn.silu(_dot(x, p["wg"], dt)).reshape(B, T, h, dv)
    return _dot((o * gate).reshape(B, T, h * dv), p["wo"], dt)


def _full_attention(p, x, s: HybridSpec, dt, attention: str):
    B, T, d = x.shape
    h = s.num_attention_heads
    hd = d // h
    q = _rms_norm(_dot(x, p["wq"], dt), p["q_norm"], s.rms_norm_eps)
    k = _rms_norm(_dot(x, p["wk"], dt), p["k_norm"], s.rms_norm_eps)
    v = _dot(x, p["wv"], dt)
    q, k, v = (t.astype(dt).reshape(B, T, h, hd) for t in (q, k, v))
    with jax.named_scope("lm.attention"):
        # lint: disable=FTL005 — a static mode string and a static length
        if resolve_attention(attention, T) == "flash":
            from fedtorch_tpu.ops.pallas.flash_attention import (
                flash_attention,
            )
            out = flash_attention(q, k, v, causal=True)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32) \
                / math.sqrt(hd)
            mask = jnp.tril(jnp.ones((T, T), bool))
            probs = jax.nn.softmax(
                jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dt), v,
                             preferred_element_type=jnp.float32)
    return _dot(out.reshape(B, T, d), p["wo"], dt)


def _mlp(p, x, dt):
    with jax.named_scope("lm.mlp"):
        return _dot(jax.nn.silu(_dot(x, p["gate"], dt))
                    * _dot(x, p["up"], dt), p["down"], dt)


def _layer(p, x, kind: str, s: HybridSpec, dt, attention: str):
    # lint: disable=FTL005 — the layer's kind is a string of the spec
    if kind == "linear_attention":
        mixed = _linear_attention(p["mixer"], x, s, dt)
    else:
        mixed = _full_attention(p["mixer"], x, s, dt, attention)
    # the residual stream stays float32: a sublayer's normed output is
    # of unit size beside an embedding of 0.02, and bfloat16's spacing
    # near 1 would round a tenth of the embedding away
    x = x + _rms_norm(mixed, p["mixer_norm"], s.rms_norm_eps)
    return x + _rms_norm(_mlp(p["mlp"], x, dt), p["mlp_norm"],
                         s.rms_norm_eps)


def hidden_states(params, x, s: HybridSpec, dt, attention: str,
                  remat: bool):
    """Token ids ``[B, T]`` -> the last layer's output ``[B, T, D]``."""
    h = params["embed"][x]
    for i, kind in enumerate(s.layer_types):
        fn = lambda p, h, kind=kind: _layer(p, h, kind, s, dt, attention)
        h = (jax.checkpoint(fn) if remat else fn)(params[f"layer_{i}"], h)
    return h


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

    def init(self, rng):
        return _jitted_init(self.module)(rng)

    def apply(self, params, x, train: bool = False, rng=None, carry=None):
        dt = jnp.dtype(self.dtype)
        h = hidden_states(params, x, self.module, dt, self.attention,
                          self.remat)
        with jax.named_scope("lm.head"):
            return logits_of(params, h, self.module, dt)

    def token_loss(self, params, x, train: bool = False, rng=None):
        """(mean next-token cross-entropy, top-1) over the B x (T - 1)
        positions of ``x`` that have a next token."""
        dt = jnp.dtype(self.dtype)
        h = hidden_states(params, x, self.module, dt, self.attention,
                          self.remat)
        with jax.named_scope("lm.head"):
            nll, hit = next_token_stats(
                logits_of(params, h, self.module, dt), x)
            return jnp.mean(nll), jnp.mean(hit)

    def init_carry(self, batch_size: int):
        return None
