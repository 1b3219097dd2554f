"""WideResNet (ref: nonconvex/wideresnet.py, factory :135-144).

WRN(depth, widen_factor, drop_rate): n=(depth-4)/6 blocks per stage,
widths [16, 16k, 32k, 64k], pre-activation basic blocks with optional
dropout between the convolutions, global average pool + linear head.
"""
from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from fedtorch_tpu.models.common import (
    make_norm, norm_f32, num_classes_of,
)


class _WideBasic(nn.Module):
    planes: int
    stride: int = 1
    drop_rate: float = 0.0
    norm: str = "bn"
    dtype: str = "float32"

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = jnp.dtype(self.dtype)
        # explicit Conv_N names pin the parameter tree (see resnet.py)
        y = norm_f32(self.norm, x, dt)
        y = nn.relu(y)
        shortcut_src = y if (self.stride != 1
                             or x.shape[-1] != self.planes) else x
        y = nn.Conv(self.planes, (3, 3), strides=(self.stride, self.stride),
                    padding=1, use_bias=False, dtype=dt, name="Conv_0")(y)
        y = norm_f32(self.norm, y, dt)
        y = nn.relu(y)
        y = nn.Dropout(rate=self.drop_rate, deterministic=not train)(y)
        y = nn.Conv(self.planes, (3, 3), padding=1, use_bias=False,
                    dtype=dt, name="Conv_1")(y)
        if self.stride != 1 or x.shape[-1] != self.planes:
            shortcut = nn.Conv(self.planes, (1, 1),
                               strides=(self.stride, self.stride),
                               use_bias=False, dtype=dt,
                               name="Conv_2")(shortcut_src)
        else:
            shortcut = x
        return y + shortcut.astype(dt)


class WideResNet(nn.Module):
    dataset: str
    depth: int = 28
    widen_factor: int = 4
    drop_rate: float = 0.0
    norm: str = "bn"
    dtype: str = "float32"
    remat: bool = False  # per-block jax.checkpoint (see resnet.py)

    @nn.compact
    def __call__(self, x, train: bool = False):
        if (self.depth - 4) % 6 != 0:
            raise ValueError("wideresnet depth must be 6n+4")
        dt = jnp.dtype(self.dtype)
        n = (self.depth - 4) // 6
        k = self.widen_factor
        # explicit names keep the param tree identical across the toggle
        block = nn.remat(_WideBasic, static_argnums=(2,)) if self.remat \
            else _WideBasic
        x = nn.Conv(16, (3, 3), padding=1, use_bias=False, dtype=dt,
                    name="Conv_0")(x.astype(dt))
        bi = 0
        for stage, planes in enumerate((16 * k, 32 * k, 64 * k)):
            for i in range(n):
                stride = 2 if (stage > 0 and i == 0) else 1
                x = block(planes=planes, stride=stride,
                          drop_rate=self.drop_rate, norm=self.norm,
                          dtype=self.dtype,
                          name=f"_WideBasic_{bi}")(x, train)
                bi += 1
        x = nn.relu(make_norm(self.norm)(x.astype(jnp.float32)))
        x = x.mean(axis=(1, 2))
        return nn.Dense(num_classes_of(self.dataset))(x)


def build_wideresnet(arch: str, dataset: str, widen_factor: int,
                     drop_rate: float, norm: str = "bn",
                     dtype: str = "float32",
                     remat: bool = False) -> nn.Module:
    """arch string 'wideresnet<depth>' (factory wideresnet.py:135-144)."""
    depth = int(arch.replace("wideresnet", ""))
    return WideResNet(dataset=dataset, depth=depth,
                      widen_factor=widen_factor, drop_rate=drop_rate,
                      norm=norm, dtype=dtype, remat=remat)
