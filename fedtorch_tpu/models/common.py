"""Shared model utilities: dataset dims, norm layers, model definition API.

The reference resolves per-dataset input/output dims inside each model
(e.g. logistic_regression.py:34-72, mlp.py:33-48, cnn.py:25-52); here the
tables live in one place.

Normalization: the reference uses BatchNorm. For a federated TPU program we
keep **all** model state in params (no mutable running-stat collections to
thread through collectives), so BN is provided in its
``track_running_stats=False`` form — normalize by the *current* batch
statistics with learned scale/shift — which is exactly what the reference's
MLP uses (mlp.py:25) and what its federated aggregation effectively assumes
(running stats are never aggregated, SURVEY.md §2.6). GroupNorm is offered
as the TPU-friendly alternative (``ModelConfig.norm='gn'``).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

# (num_features, num_classes) for convex models
# (ref: logistic_regression.py:34-72).
CONVEX_DIMS = {
    "epsilon": (2000, 2),
    "url": (3231961, 2),
    "rcv1": (47236, 2),
    "higgs": (28, 2),
    "mnist": (784, 10),
    "emnist": (784, 10),
    "emnist_full": (784, 62),
    "cifar10": (3072, 10),
    "cifar100": (3072, 100),
    "fashion_mnist": (784, 10),
    "synthetic": (60, 10),
    "adult": (14, 2),
}

# regression dims (ref: least_square.py:27-41); num_classes == 1.
REGRESSION_DIMS = {
    "epsilon": 2000,
    "url": 3231961,
    "rcv1": 47236,
    "MSD": 90,
    "synthetic": 60,
}


def num_classes_of(dataset: str) -> int:
    """ref: mlp.py:33-41 / cnn.py:31-37 / resnet.py ResNetBase."""
    table = {
        "cifar10": 10, "mnist": 10, "fashion_mnist": 10, "emnist": 10,
        "stl10": 10, "cifar100": 100, "emnist_full": 62, "adult": 2,
        "synthetic": 10, "higgs": 2, "epsilon": 2, "rcv1": 2,
        "shakespeare": 86, "imagenet": 1000,
    }
    if dataset not in table:
        raise ValueError(f"No class count known for dataset {dataset!r}")
    return table[dataset]


def flat_input_size(dataset: str) -> int:
    """ref: mlp.py:43-48."""
    if "cifar" in dataset or dataset == "stl10":
        return 32 * 32 * 3 if "cifar" in dataset else 96 * 96 * 3
    if "mnist" in dataset:
        return 28 * 28
    if dataset == "adult":
        return 14
    if dataset == "synthetic":
        return 60
    if dataset == "higgs":
        return 28
    if dataset == "epsilon":
        return 2000
    if dataset == "rcv1":
        return 47236
    raise NotImplementedError(f"No flat input size for {dataset!r}")


def image_shape(dataset: str):
    """NHWC sample shape for conv models."""
    if "cifar" in dataset:
        return (32, 32, 3)
    if "mnist" in dataset:
        return (28, 28, 1)
    if dataset == "stl10":
        return (96, 96, 3)
    raise NotImplementedError(f"No image shape for {dataset!r}")


class BatchStatsNorm(nn.Module):
    """BatchNorm with ``track_running_stats=False`` semantics: always uses
    the current batch statistics, keeps only scale/shift in params.
    Normalizes over all axes except the trailing channel axis."""
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        reduce_axes = tuple(i for i in range(x.ndim) if i != x.ndim - 1)
        mean = jnp.mean(x, axis=reduce_axes, keepdims=True)
        var = jnp.var(x, axis=reduce_axes, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        return y * scale + bias


def norm_f32(kind: str, x, dtype):
    """Normalize in float32 for stability, return in the compute dtype
    (shared mixed-precision norm policy for the conv/dense zoo)."""
    return make_norm(kind)(x.astype(jnp.float32)).astype(dtype)


def make_norm(kind: str):
    """Norm factory: 'bn' -> batch-stats norm, 'gn' -> GroupNorm."""
    if kind == "bn":
        return BatchStatsNorm()
    if kind == "gn":
        return _GN()
    raise ValueError(f"Unknown norm kind {kind!r}")


class _GN(nn.Module):
    @nn.compact
    def __call__(self, x):
        groups = 32
        while x.shape[-1] % groups != 0:
            groups //= 2
        return nn.GroupNorm(num_groups=max(groups, 1))(x)


def is_token_model(model) -> bool:
    """A model that makes its target from the batch itself (the next
    token, models/hybrid_lm.py): its loss is ``model.token_loss(params,
    x)`` and a row's label takes no part in it.

    What such a model reports on the round's row (``metrics.jsonl``) is
    its own business: the engine (``parallel/federated.py``, ``cli.py``)
    names none of the keys. A token model MAY give, and the engine
    reads with one ``getattr`` each:

    * ``gauge_names`` — a static tuple of row keys, known from the
      model's specification before anything is traced; empty where its
      loss reports no parts. Where it is not, the sequential round asks
      ``local_step`` for the parts (``with_parts``).
    * ``round_gauges(parts)`` — from ``token_loss_parts``' parts
      stacked over the round's clients and steps (``[k, K, ...]``) a
      tuple of float32 scalars in ``gauge_names``' order. It runs
      inside the round program; the tuple is
      ``RoundMetrics.model_gauges`` and rides the round's one scalar
      fetch under those keys.
    * ``trace_gauges(rows, tokens)`` — one dict of the host floats that
      are known when a ``rows x tokens`` training step is traced (what
      the step keeps, which kernels it takes), merged into the row as
      it is.

    A new key is written in the model's file and documented in
    ``telemetry/schema.py`` and docs/observability.md, nowhere else."""
    return getattr(model, "token_loss", None) is not None


class ModelDef(NamedTuple):
    """A model as pure functions — replaces the reference's nn.Module
    objects held by each Client (nodes/nodes.py:43-62).

    ``apply(params, x, train=..., rng=..., carry=...)`` returns ``logits``
    for feed-forward models and ``(logits, new_carry)`` when
    ``is_recurrent`` (the GRU's hidden state is carried explicitly through
    the training scan — SURVEY.md §7 'hard parts')."""
    name: str
    module: Any
    sample_input: jnp.ndarray
    is_recurrent: bool = False
    is_regression: bool = False
    has_noise_param: bool = False  # robust_* adversarial input noise
    # model sows regularizers into the 'aux_loss' collection (MoE
    # load-balance); consumed via apply_with_aux when the config weight
    # is non-zero, silently discarded by plain apply
    has_aux_loss: bool = False

    def init(self, rng) -> Any:
        rngs = {"params": rng, "dropout": jax.random.fold_in(rng, 1)}
        if self.is_recurrent:
            carry = self.init_carry(self.sample_input.shape[0])
            return self.module.init(rngs, self.sample_input, carry)["params"]
        return self.module.init(rngs, self.sample_input)["params"]

    def apply(self, params, x, train: bool = False, rng=None, carry=None):
        rngs = {"dropout": rng} if rng is not None else None
        kwargs = dict(train=train) if not self.is_recurrent else {}
        if self.is_recurrent:
            return self.module.apply({"params": params}, x, carry, rngs=rngs)
        return self.module.apply({"params": params}, x, rngs=rngs, **kwargs)

    def apply_with_aux(self, params, x, train: bool = False, rng=None):
        """Forward returning ``(logits, aux)`` where ``aux`` is the SUM
        of everything the model sowed into the 'aux_loss' collection
        (Switch sums the per-layer load-balance losses, arXiv:2101.03961
        §2.2). Feed-forward models only."""
        rngs = {"dropout": rng} if rng is not None else None
        out, var = self.module.apply({"params": params}, x, rngs=rngs,
                                     train=train, mutable=["aux_loss"])
        leaves = jax.tree.leaves(var.get("aux_loss", {}))
        aux = sum(leaves) if leaves else jnp.asarray(0.0)
        return out, aux

    def init_carry(self, batch_size: int):
        if not self.is_recurrent:
            return None
        return self.module.initial_carry(batch_size)
