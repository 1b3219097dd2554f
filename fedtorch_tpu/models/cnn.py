"""LeNet-style CNN (ref: nonconvex/cnn.py:9-69).

conv(20,5x5,valid) -> relu -> maxpool2 -> conv(50,5x5,valid) -> relu ->
maxpool2 -> fc512 -> fc num_classes. NHWC layout (TPU-native) instead of
the reference's NCHW; the flattened representation size matches
cnn.py:45-52 (4*4*50 mnist / 5*5*50 cifar).
"""
from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from fedtorch_tpu.models.common import num_classes_of


class CNN(nn.Module):
    dataset: str
    dtype: str = "float32"

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = jnp.dtype(self.dtype)
        # explicit Conv_N names pin the parameter tree (see resnet.py)
        x = x.astype(dt)
        x = nn.Conv(20, (5, 5), padding="VALID", dtype=dt, use_bias=True,
                    name="Conv_0")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(50, (5, 5), padding="VALID", dtype=dt, use_bias=True,
                    name="Conv_1")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(512, dtype=dt)(x))
        return nn.Dense(num_classes_of(self.dataset))(
            x.astype(jnp.float32))
