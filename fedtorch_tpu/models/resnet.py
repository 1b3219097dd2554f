"""ResNet for CIFAR (6n+2) and ImageNet depths (ref: nonconvex/resnet.py).

* CIFAR variant (resnet.py:209-257): 3x3 stem, 16/32/64 planes, three
  stages of (size-2)//6 blocks; BasicBlock below depth 44, Bottleneck from
  44 up; global average pool + linear head.
* ImageNet variant (resnet.py:145-206): 7x7/2 stem + maxpool, 64/128/256/512
  planes, depths 18/34/50/101/152.
* The factory parses the depth out of the arch string and picks the variant
  from the dataset family (resnet.py:260-274).

NHWC + configurable norm ('bn' = batch-stats norm, 'gn' = GroupNorm; see
models/common.py). ``dtype='bfloat16'`` runs convs/matmuls in bf16 on the
MXU while keeping parameters and normalization statistics in float32.
"""
from __future__ import annotations

from typing import Type

import flax.linen as nn
import jax.numpy as jnp

from fedtorch_tpu.models.common import norm_f32 as _norm32, num_classes_of


class BasicBlock(nn.Module):
    planes: int
    stride: int = 1
    norm: str = "bn"
    dtype: str = "float32"
    expansion = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = jnp.dtype(self.dtype)
        # explicit Conv_N names (= nn.Conv's auto-names) pin the
        # parameter tree that checkpoints hold
        # (tests/data/model_param_trees.json)
        residual = x
        y = nn.Conv(self.planes, (3, 3), strides=(self.stride, self.stride),
                    padding=1, use_bias=False, dtype=dt, name="Conv_0")(x)
        y = _norm32(self.norm, y, dt)
        y = nn.relu(y)
        y = nn.Conv(self.planes, (3, 3), padding=1, use_bias=False,
                    dtype=dt, name="Conv_1")(y)
        y = _norm32(self.norm, y, dt)
        if self.stride != 1 or x.shape[-1] != self.planes:
            residual = nn.Conv(self.planes, (1, 1),
                               strides=(self.stride, self.stride),
                               use_bias=False, dtype=dt, name="Conv_2")(x)
            residual = _norm32(self.norm, residual, dt)
        return nn.relu(y + residual)


class Bottleneck(nn.Module):
    planes: int
    stride: int = 1
    norm: str = "bn"
    dtype: str = "float32"
    expansion = 4

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = jnp.dtype(self.dtype)
        residual = x
        out_planes = self.planes * self.expansion
        y = nn.Conv(self.planes, (1, 1), use_bias=False, dtype=dt,
                    name="Conv_0")(x)
        y = _norm32(self.norm, y, dt)
        y = nn.relu(y)
        y = nn.Conv(self.planes, (3, 3), strides=(self.stride, self.stride),
                    padding=1, use_bias=False, dtype=dt, name="Conv_1")(y)
        y = _norm32(self.norm, y, dt)
        y = nn.relu(y)
        y = nn.Conv(out_planes, (1, 1), use_bias=False, dtype=dt,
                    name="Conv_2")(y)
        y = _norm32(self.norm, y, dt)
        if self.stride != 1 or x.shape[-1] != out_planes:
            residual = nn.Conv(out_planes, (1, 1),
                               strides=(self.stride, self.stride),
                               use_bias=False, dtype=dt, name="Conv_3")(x)
            residual = _norm32(self.norm, residual, dt)
        return nn.relu(y + residual)


class ResNetCifar(nn.Module):
    dataset: str
    size: int
    norm: str = "bn"
    dtype: str = "float32"
    # per-residual-block rematerialization (jax.checkpoint): backward
    # recomputes each block's activations instead of storing them —
    # ~1.33x the FLOPs for activation memory that scales with ONE block
    # instead of the depth. The HBM<->FLOPs trade SURVEY.md's TPU notes
    # call for; gradients are bitwise the same computation graph values.
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.size % 6 != 2:
            raise ValueError(f"resnet_size must be 6n+2, got {self.size}")
        dt = jnp.dtype(self.dtype)
        x = x.astype(dt)
        n_blocks = (self.size - 2) // 6
        base: Type = Bottleneck if self.size >= 44 else BasicBlock
        # explicit names matching the plain auto-names so the param tree
        # is IDENTICAL with remat on or off (checkpoints stay loadable
        # across the toggle; remat wrappers auto-name differently)
        block = nn.remat(base, static_argnums=(2,)) if self.remat \
            else base  # train (arg 2, counting self) is static
        x = nn.Conv(16, (3, 3), padding=1, use_bias=False, dtype=dt,
                    name="Conv_0")(x)
        x = _norm32(self.norm, x, dt)
        x = nn.relu(x)
        bi = 0
        for stage, planes in enumerate((16, 32, 64)):
            for i in range(n_blocks):
                stride = 2 if (stage > 0 and i == 0) else 1
                x = block(planes=planes, stride=stride, norm=self.norm,
                          dtype=self.dtype,
                          name=f"{base.__name__}_{bi}")(x, train)
                bi += 1
        x = x.mean(axis=(1, 2))
        # classifier head in f32 for logit fidelity
        return nn.Dense(num_classes_of(self.dataset))(
            x.astype(jnp.float32))


class ResNetImageNet(nn.Module):
    dataset: str
    size: int
    norm: str = "bn"
    dtype: str = "float32"
    remat: bool = False  # see ResNetCifar.remat

    _PARAMS = {
        18: (BasicBlock, (2, 2, 2, 2)),
        34: (BasicBlock, (3, 4, 6, 3)),
        50: (Bottleneck, (3, 4, 6, 3)),
        101: (Bottleneck, (3, 4, 23, 3)),
        152: (Bottleneck, (3, 8, 36, 3)),
    }

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = jnp.dtype(self.dtype)
        x = x.astype(dt)
        base, layers = self._PARAMS[self.size]
        # explicit names: identical param tree with remat on/off (above)
        block = nn.remat(base, static_argnums=(2,)) if self.remat \
            else base
        x = nn.Conv(64, (7, 7), strides=(2, 2), padding=3, use_bias=False,
                    dtype=dt, name="Conv_0")(x)
        x = _norm32(self.norm, x, dt)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        bi = 0
        for stage, (planes, n_blocks) in enumerate(
                zip((64, 128, 256, 512), layers)):
            for i in range(n_blocks):
                stride = 2 if (stage > 0 and i == 0) else 1
                x = block(planes=planes, stride=stride, norm=self.norm,
                          dtype=self.dtype,
                          name=f"{base.__name__}_{bi}")(x, train)
                bi += 1
        x = x.mean(axis=(1, 2))
        return nn.Dense(num_classes_of(self.dataset))(
            x.astype(jnp.float32))


def build_resnet(arch: str, dataset: str, norm: str = "bn",
                 dtype: str = "float32", remat: bool = False) -> nn.Module:
    """Factory matching resnet.py:260-274 arch-string parsing."""
    size = int(arch.replace("resnet", ""))
    if "cifar" in dataset or "svhn" in dataset \
            or "downsampled_imagenet" in dataset or dataset == "stl10":
        return ResNetCifar(dataset=dataset, size=size, norm=norm,
                           dtype=dtype, remat=remat)
    if "imagenet" in dataset:
        return ResNetImageNet(dataset=dataset, size=size, norm=norm,
                              dtype=dtype, remat=remat)
    raise NotImplementedError(
        f"resnet supports cifar/imagenet-family datasets, got {dataset!r}")
