"""DenseNet with optional BC mode (ref: nonconvex/densenet.py, factory
:200-208).

DenseNet(depth, growth_rate, bc_mode, compression): dense blocks of
[norm->relu->(1x1 bottleneck if BC)->3x3 conv] layers with channel
concatenation, transition layers with compression, global pool + head.
"""
from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from fedtorch_tpu.models.common import (
    make_norm, norm_f32, num_classes_of,
)


class _DenseLayer(nn.Module):
    growth_rate: int
    bc_mode: bool
    drop_rate: float = 0.0
    norm: str = "bn"
    dtype: str = "float32"

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = jnp.dtype(self.dtype)
        # explicit Conv_N names = nn.Conv auto-names (which depend on
        # bc_mode: the 3x3 is Conv_1 after a bottleneck, Conv_0 alone):
        # they pin the parameter tree (see resnet.py)
        y = nn.relu(norm_f32(self.norm, x, dt))
        if self.bc_mode:
            y = nn.Conv(4 * self.growth_rate, (1, 1), use_bias=False,
                        dtype=dt, name="Conv_0")(y)
            y = nn.relu(norm_f32(self.norm, y, dt))
        y = nn.Conv(self.growth_rate, (3, 3), padding=1, use_bias=False,
                    dtype=dt,
                    name="Conv_1" if self.bc_mode else "Conv_0")(y)
        y = nn.Dropout(rate=self.drop_rate, deterministic=not train)(y)
        return jnp.concatenate([x.astype(dt), y], axis=-1)


class DenseNet(nn.Module):
    dataset: str
    depth: int = 40
    growth_rate: int = 12
    bc_mode: bool = False
    compression: float = 1.0
    drop_rate: float = 0.0
    norm: str = "bn"
    dtype: str = "float32"
    remat: bool = False  # per-layer jax.checkpoint (see resnet.py)

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = jnp.dtype(self.dtype)
        layers_per_block = (self.depth - 4) // 3
        if self.bc_mode:
            layers_per_block //= 2
        ch = 2 * self.growth_rate if self.bc_mode else 16
        # explicit names keep the param tree identical across the toggle
        layer = nn.remat(_DenseLayer, static_argnums=(2,)) if self.remat \
            else _DenseLayer
        x = nn.Conv(ch, (3, 3), padding=1, use_bias=False, dtype=dt,
                    name="Conv_0")(x.astype(dt))
        li = 0
        for block in range(3):
            for _ in range(layers_per_block):
                x = layer(growth_rate=self.growth_rate,
                          bc_mode=self.bc_mode,
                          drop_rate=self.drop_rate, norm=self.norm,
                          dtype=self.dtype,
                          name=f"_DenseLayer_{li}")(x, train)
                li += 1
            if block < 2:
                out_ch = int(x.shape[-1] * self.compression)
                x = nn.relu(norm_f32(self.norm, x, dt))
                x = nn.Conv(out_ch, (1, 1), use_bias=False, dtype=dt,
                            name=f"Conv_{block + 1}")(x)
                x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.relu(make_norm(self.norm)(x.astype(jnp.float32)))
        x = x.mean(axis=(1, 2))
        return nn.Dense(num_classes_of(self.dataset))(x)


def build_densenet(arch: str, dataset: str, growth_rate: int, bc_mode: bool,
                   compression: float, drop_rate: float,
                   norm: str = "bn", dtype: str = "float32",
                   remat: bool = False) -> nn.Module:
    """arch string 'densenet<depth>' (factory densenet.py:200-208)."""
    depth = int(arch.replace("densenet", ""))
    return DenseNet(dataset=dataset, depth=depth, growth_rate=growth_rate,
                    bc_mode=bc_mode,
                    compression=compression if bc_mode else 1.0,
                    drop_rate=drop_rate, norm=norm, dtype=dtype,
                    remat=remat)
