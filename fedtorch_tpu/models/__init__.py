"""Model registry and factory.

``define_model`` mirrors the reference dispatch (components/model.py:7-23):
prefix matching for resnet/wideresnet/densenet arch strings, exact names
otherwise. Cross-rank init consistency (model.py:33-43 zeroes non-rank-0
params and all-reduces) is unnecessary here: a single shared PRNG key
initializes params once; replication is handled by sharding.
"""
from __future__ import annotations

import jax.numpy as jnp

from fedtorch_tpu.config import ExperimentConfig
from fedtorch_tpu.models.cnn import CNN
from fedtorch_tpu.models.common import (
    CONVEX_DIMS, REGRESSION_DIMS, ModelDef, flat_input_size, image_shape,
    num_classes_of,
)
from fedtorch_tpu.models.densenet import DenseNet, build_densenet
from fedtorch_tpu.models.linear import (
    LeastSquare, LinearMAFL, LogisticRegression,
)
from fedtorch_tpu.models.mlp import MLP
from fedtorch_tpu.models.resnet import (
    ResNetCifar, ResNetImageNet, build_resnet,
)
from fedtorch_tpu.models.rnn import CharGRU
from fedtorch_tpu.models.wideresnet import WideResNet, build_wideresnet

MODEL_NAMES = (
    "logistic_regression", "robust_logistic_regression", "least_square",
    "robust_least_square", "mlp", "robust_mlp", "cnn", "rnn",
    "transformer", "hybrid_lm",
    # prefix families:
    "resnet*", "wideresnet*", "densenet*",
)


def _sample_flat(dataset: str, batch: int = 2, synthetic_dim: int = 60):
    if dataset == "synthetic":
        return jnp.zeros((batch, synthetic_dim), jnp.float32)
    return jnp.zeros((batch, flat_input_size(dataset)), jnp.float32)


def _sample_image(dataset: str, batch: int = 2):
    return jnp.zeros((batch,) + image_shape(dataset), jnp.float32)


def _sample_regression(dataset: str, batch: int, synthetic_dim: int):
    dim = synthetic_dim if dataset == "synthetic" \
        else REGRESSION_DIMS[dataset]
    return jnp.zeros((batch, dim), jnp.float32)


def define_model(cfg: ExperimentConfig, batch_size: int = 2) -> ModelDef:
    """Build a :class:`ModelDef` from config (ref dispatch model.py:7-23)."""
    arch = cfg.model.arch
    dataset = cfg.data.dataset
    m = cfg.model
    if arch == "hybrid_lm":
        # the shape comes from a file with the public config.json's
        # keys, not from the RNN's fields
        from fedtorch_tpu.models.hybrid_lm import HybridLM, load_spec
        if not m.spec_file:
            raise ValueError(
                "arch 'hybrid_lm' reads its shape from a file: pass "
                "--model_spec <config.json>")
        return HybridLM(arch, load_spec(m.spec_file),
                        dtype=cfg.mesh.compute_dtype,
                        attention=m.attention, remat=cfg.mesh.remat)
    if cfg.mesh.remat and not (
            arch.startswith(("resnet", "wideresnet", "densenet"))
            or arch == "transformer"):
        import warnings
        warnings.warn(
            f"--remat has no effect for arch {arch!r} (supported: "
            "resnet*/wideresnet*/densenet*/transformer — the deep "
            "activation-heavy families); running without "
            "rematerialization", stacklevel=2)
    if arch.startswith("wideresnet"):
        module = build_wideresnet(arch, dataset, m.wideresnet_widen_factor,
                                  m.drop_rate, m.norm,
                                  dtype=cfg.mesh.compute_dtype,
                                  remat=cfg.mesh.remat)
        return ModelDef(arch, module, _sample_image(dataset, batch_size))
    if arch.startswith("resnet"):
        module = build_resnet(arch, dataset, m.norm,
                              dtype=cfg.mesh.compute_dtype,
                              remat=cfg.mesh.remat)
        return ModelDef(arch, module, _sample_image(dataset, batch_size))
    if arch.startswith("densenet"):
        module = build_densenet(arch, dataset, m.densenet_growth_rate,
                                m.densenet_bc_mode, m.densenet_compression,
                                m.drop_rate, m.norm,
                                dtype=cfg.mesh.compute_dtype,
                                remat=cfg.mesh.remat)
        return ModelDef(arch, module, _sample_image(dataset, batch_size))
    if arch == "logistic_regression":
        return ModelDef(arch, LogisticRegression(
            dataset=dataset, dtype=cfg.mesh.compute_dtype),
                        _sample_flat(dataset, batch_size,
                                     cfg.data.synthetic_dim))
    if arch == "robust_logistic_regression":
        return ModelDef(arch, LogisticRegression(
            dataset=dataset, robust=True, dtype=cfg.mesh.compute_dtype),
                        _sample_flat(dataset, batch_size,
                                     cfg.data.synthetic_dim),
                        has_noise_param=True)
    if arch == "least_square":
        return ModelDef(arch, LeastSquare(dataset=dataset,
                                          dtype=cfg.mesh.compute_dtype),
                        _sample_regression(dataset, batch_size,
                                           cfg.data.synthetic_dim),
                        is_regression=True)
    if arch == "robust_least_square":
        return ModelDef(arch, LeastSquare(dataset=dataset, robust=True,
                                          dtype=cfg.mesh.compute_dtype),
                        _sample_regression(dataset, batch_size,
                                           cfg.data.synthetic_dim),
                        is_regression=True, has_noise_param=True)
    if arch == "mlp":
        module = MLP(dataset=dataset, num_layers=m.mlp_num_layers,
                     hidden_size=m.mlp_hidden_size, drop_rate=m.drop_rate,
                     norm=m.norm, dtype=cfg.mesh.compute_dtype)
        return ModelDef(arch, module,
                        _sample_flat(dataset, batch_size,
                                     cfg.data.synthetic_dim))
    if arch == "robust_mlp":
        module = MLP(dataset=dataset, num_layers=m.mlp_num_layers,
                     hidden_size=m.mlp_hidden_size, drop_rate=m.drop_rate,
                     norm=m.norm, robust=True,
                     dtype=cfg.mesh.compute_dtype)
        return ModelDef(arch, module,
                        _sample_flat(dataset, batch_size,
                                     cfg.data.synthetic_dim),
                        has_noise_param=True)
    if arch == "cnn":
        return ModelDef(arch,
                        CNN(dataset=dataset,
                            dtype=cfg.mesh.compute_dtype),
                        _sample_image(dataset, batch_size))
    if arch == "rnn":
        module = CharGRU(vocab_size=m.vocab_size,
                         hidden_size=m.rnn_hidden_size,
                         dtype=cfg.mesh.compute_dtype)
        sample = jnp.zeros((batch_size, m.rnn_seq_len), jnp.int32)
        return ModelDef(arch, module, sample, is_recurrent=True)
    if arch == "transformer":
        from fedtorch_tpu.models.transformer import TransformerLM
        d_model = m.rnn_hidden_size * 2
        # head count must divide the width; degrade gracefully for odd
        # hidden sizes instead of crashing in attention
        num_heads = next(h for h in (4, 2, 1) if d_model % h == 0)
        if m.moe_experts >= 8 and m.moe_capacity_factor == 0:
            import warnings
            warnings.warn(
                f"--moe_experts {m.moe_experts} with dense dispatch "
                f"executes {m.moe_experts}x the expert-MLP FLOPs "
                "(exactness-oracle mode). For training at scale set "
                "--moe_capacity_factor 1.25: E/cf times fewer "
                "executed expert FLOPs with bounded token drop "
                "(docs/performance.md 'Dispatch A/B')",
                stacklevel=2)
        module = TransformerLM(vocab_size=m.vocab_size, d_model=d_model,
                               num_heads=num_heads,
                               num_layers=m.mlp_num_layers,
                               dtype=cfg.mesh.compute_dtype,
                               num_experts=m.moe_experts,
                               capacity_factor=m.moe_capacity_factor,
                               attention=m.attention,
                               remat=cfg.mesh.remat)
        sample = jnp.zeros((batch_size, m.rnn_seq_len), jnp.int32)
        return ModelDef(arch, module, sample,
                        has_aux_loss=m.moe_experts > 0)
    raise ValueError(f"Unknown architecture {arch!r}")
