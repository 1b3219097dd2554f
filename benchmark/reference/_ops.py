"""Plain float32 building blocks shared by the reference models.

Everything here is ``jax.numpy`` / ``lax.conv_general_dilated`` under
``jax.default_matmul_precision("highest")`` (set by the caller around
the whole reference): no flax, no kernels, nothing imported from the
program. ``cast`` is the control's hook: a function applied to the two
operands of every convolution and matrix product (identity for the
reference; a round-trip through float8 for the control that computes
below the precision the configuration states).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def identity(x):
    return x


def fp8_round_trip(x):
    """Operand rounded to float8 e4m3 after scaling into its range (the
    per-tensor scaling an fp8 path would apply), back to float32."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def bf16_round_trip(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def conv(x, kernel, stride=1, padding=0, cast=identity):
    """NHWC x HWIO convolution; ``padding`` an int or 'VALID'."""
    pad = padding if isinstance(padding, str) \
        else ((padding, padding), (padding, padding))
    return lax.conv_general_dilated(
        cast(x), cast(kernel), (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def dense(x, kernel, bias, cast=identity):
    return jnp.dot(cast(x), cast(kernel),
                   precision=lax.Precision.HIGHEST) + bias


def batch_stats_norm(x, scale, bias, eps=1e-5):
    """BatchNorm with ``track_running_stats=False``: the current
    batch's statistics over every axis but the channel, learned
    scale/shift."""
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def softmax_cross_entropy(logits, labels):
    """Mean over the batch of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                 axis=-1)[:, 0]
    return -jnp.mean(picked)
