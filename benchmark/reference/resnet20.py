"""ResNet-20 for CIFAR (He et al. 2016, section 4.2: 6n+2 layers, n = 3,
widths 16/32/64, 3x3 convolutions, 1x1 projection shortcuts where the
shape changes, global average pool, linear head), written out in plain
float32. Normalization is BatchNorm on the current batch's statistics
with no running averages, as the program's federated models use it
(nothing but scale/shift is state). Parameters arrive as the nested
dict the launcher's model initialises, by name.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import _ops

WIDTHS = (16, 32, 64)
BLOCKS_PER_STAGE = 3


def _bn(p, x):
    return _ops.batch_stats_norm(x, p["scale"], p["bias"])


def _block(p, x, width, stride, cast):
    y = _ops.conv(x, p["Conv_0"]["kernel"], stride, 1, cast)
    y = jnp.maximum(_bn(p["BatchStatsNorm_0"], y), 0.0)
    y = _ops.conv(y, p["Conv_1"]["kernel"], 1, 1, cast)
    y = _bn(p["BatchStatsNorm_1"], y)
    if stride != 1 or x.shape[-1] != width:
        x = _ops.conv(x, p["Conv_2"]["kernel"], stride, 0, cast)
        x = _bn(p["BatchStatsNorm_2"], x)
    return jnp.maximum(y + x, 0.0)


def forward(params, x, cast=_ops.identity):
    """``x``: [B, 32, 32, 3] float32 -> logits [B, 10]."""
    x = _ops.conv(x.astype(jnp.float32), params["Conv_0"]["kernel"], 1, 1,
                  cast)
    x = jnp.maximum(_bn(params["BatchStatsNorm_0"], x), 0.0)
    i = 0
    for stage, width in enumerate(WIDTHS):
        for b in range(BLOCKS_PER_STAGE):
            stride = 2 if (stage > 0 and b == 0) else 1
            x = _block(params[f"BasicBlock_{i}"], x, width, stride, cast)
            i += 1
    x = jnp.mean(x, axis=(1, 2))
    # the program keeps the classifier head in float32 whatever the
    # compute type: the control's cast does not apply to it either
    return _ops.dense(x, params["Dense_0"]["kernel"],
                      params["Dense_0"]["bias"])


def loss(params, x, y, cast=_ops.identity):
    return _ops.softmax_cross_entropy(forward(params, x, cast), y)
