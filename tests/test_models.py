"""Model zoo tests: shapes, param counts vs the torch reference, and
jit/vmap usability of every architecture."""
import json
import os
import sys
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from fedtorch_tpu.config import DataConfig, ExperimentConfig, ModelConfig
from fedtorch_tpu.models import define_model

sys.path.insert(0, "/root/reference")


def _cfg(arch, dataset, **model_kw):
    return ExperimentConfig(data=DataConfig(dataset=dataset),
                            model=ModelConfig(arch=arch, **model_kw))


def _param_count(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def _torch_param_count(model):
    return sum(p.numel() for p in model.parameters())


def _ref_args(arch, dataset, **kw):
    ns = types.SimpleNamespace(
        arch=arch, data=dataset, mlp_num_layers=2, mlp_hidden_size=500,
        drop_rate=0.0, vocab_size=86, rnn_hidden_size=50, rnn_seq_len=50,
        batch_size=4, federated_type="fedavg", wideresnet_widen_factor=4,
        densenet_growth_rate=12, densenet_bc_mode=False,
        densenet_compression=0.5)
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


@pytest.mark.parametrize("arch,dataset,shape", [
    ("logistic_regression", "mnist", (4, 784)),
    ("robust_logistic_regression", "mnist", (4, 784)),
    ("least_square", "MSD", (4, 90)),
    ("robust_least_square", "MSD", (4, 90)),
    ("mlp", "mnist", (4, 784)),
    ("robust_mlp", "cifar10", (4, 3072)),
    ("cnn", "mnist", (4, 28, 28, 1)),
    ("cnn", "cifar10", (4, 32, 32, 3)),
    ("resnet20", "cifar10", (4, 32, 32, 3)),
    ("resnet50", "cifar10", (4, 32, 32, 3)),
    ("wideresnet28", "cifar10", (4, 32, 32, 3)),
    ("densenet40", "cifar10", (4, 32, 32, 3)),
])
def test_forward_shapes(arch, dataset, shape):
    model = define_model(_cfg(arch, dataset))
    params = model.init(jax.random.key(0))
    x = jnp.zeros(shape)
    out = model.apply(params, x)
    expected_classes = {"mnist": 10, "cifar10": 10, "MSD": 1}[dataset]
    assert out.shape == (4, expected_classes)


@pytest.mark.parametrize("arch,dataset,ref_builder", [
    ("logistic_regression", "mnist", "logistic_regression"),
    ("mlp", "mnist", "mlp"),
    ("cnn", "mnist", "cnn"),
    ("cnn", "cifar10", "cnn"),
    ("resnet20", "cifar10", "resnet"),
    ("resnet56", "cifar10", "resnet"),
    ("wideresnet28", "cifar10", "wideresnet"),
])
def test_param_count_matches_reference(arch, dataset, ref_builder):
    """Same trainable parameter count as the torch model => same capacity.

    BN differences: torch BatchNorm holds 2 learnable params per channel,
    as does our batch-stats norm — so counts line up exactly."""
    pytest.importorskip(
        "fedtorch",
        reason="reference checkout not mounted at /root/reference")
    import fedtorch.components.models as ref_models
    ref = ref_models.__dict__[ref_builder](_ref_args(arch, dataset))
    model = define_model(_cfg(arch, dataset))
    params = model.init(jax.random.key(0))
    assert _param_count(params) == _torch_param_count(ref)


def test_logistic_regression_zero_init():
    model = define_model(_cfg("logistic_regression", "mnist"))
    params = model.init(jax.random.key(0))
    for leaf in jax.tree.leaves(params):
        assert float(jnp.abs(leaf).max()) == 0.0


def test_robust_model_has_noise_param():
    model = define_model(_cfg("robust_logistic_regression", "mnist"))
    assert model.has_noise_param
    params = model.init(jax.random.key(0))
    assert "noise" in params
    # N(0, 0.001) init
    assert float(jnp.abs(params["noise"]).max()) < 0.01
    assert float(jnp.abs(params["noise"]).max()) > 0.0


def test_rnn_carry_threading():
    model = define_model(_cfg("rnn", "shakespeare"))
    params = model.init(jax.random.key(0))
    tokens = jnp.ones((4, 50), jnp.int32)
    carry = model.init_carry(4)
    logits, carry2 = model.apply(params, tokens, carry=carry)
    assert logits.shape == (4, 50, 86)
    assert carry2.shape == carry.shape
    # hidden state actually progresses
    assert float(jnp.max(jnp.abs(carry2))) > 0.0
    # param count parity with reference GRU: torch's cuDNN-style GRU keeps
    # redundant additive double biases (b_ih + b_hh) on the r and z gates;
    # flax's GRUCell folds them. Identical function class, 2*hidden fewer
    # raw parameters.
    pytest.importorskip(
        "fedtorch",
        reason="reference checkout not mounted at /root/reference")
    import fedtorch.components.models as ref_models
    ref = ref_models.rnn(_ref_args("rnn", "shakespeare"))
    assert _param_count(params) == _torch_param_count(ref) - 2 * 50


def test_vmap_per_client_params():
    """A batch of per-client models — the core federated layout."""
    model = define_model(_cfg("mlp", "mnist"))
    keys = jax.random.split(jax.random.key(0), 3)
    params = jax.vmap(model.init)(keys)
    x = jnp.ones((3, 5, 784))
    out = jax.vmap(lambda p, xi: model.apply(p, xi))(params, x)
    assert out.shape == (3, 5, 10)


def test_jit_forward():
    model = define_model(_cfg("resnet20", "cifar10"))
    params = model.init(jax.random.key(0))
    f = jax.jit(lambda p, x: model.apply(p, x))
    out = f(params, jnp.ones((2, 32, 32, 3)))
    assert out.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_groupnorm_variant():
    model = define_model(_cfg("resnet20", "cifar10", norm="gn"))
    params = model.init(jax.random.key(0))
    out = model.apply(params, jnp.ones((2, 32, 32, 3)))
    assert out.shape == (2, 10)


def test_dropout_needs_rng_and_is_stochastic():
    model = define_model(_cfg("mlp", "mnist", drop_rate=0.5))
    params = model.init(jax.random.key(0))
    # distinct rows: identical rows would be collapsed to zero by the
    # batch-stats norm regardless of dropout
    x = jax.random.normal(jax.random.key(0), (4, 784))
    o1 = model.apply(params, x, train=True, rng=jax.random.key(1))
    o2 = model.apply(params, x, train=True, rng=jax.random.key(2))
    assert not np.allclose(np.asarray(o1), np.asarray(o2))
    # eval is deterministic
    e1 = model.apply(params, x)
    e2 = model.apply(params, x)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2))


def _cfg_dtype(arch, dataset, dtype, **model_kw):
    from fedtorch_tpu.config import MeshConfig
    return ExperimentConfig(data=DataConfig(dataset=dataset),
                            model=ModelConfig(arch=arch, **model_kw),
                            mesh=MeshConfig(compute_dtype=dtype))


@pytest.mark.parametrize("arch,dataset", [
    ("rnn", "shakespeare"),
    ("logistic_regression", "mnist"),
    ("robust_logistic_regression", "mnist"),
    ("least_square", "MSD"),
    ("transformer", "shakespeare"),
])
def test_bf16_compute_dtype_wired(arch, dataset):
    """compute_dtype=bfloat16 must reach every model family: params stay
    f32 (mixed precision keeps master weights), the forward runs finite,
    and training (grad step) stays finite. Closes the
    models/__init__ warning path for the rnn/linear tail."""
    model = define_model(_cfg_dtype(arch, dataset, "bfloat16"))
    params = model.init(jax.random.key(0))
    # master params stay f32
    for leaf in jax.tree.leaves(params):
        assert leaf.dtype == jnp.float32, leaf.dtype
    if arch == "rnn":
        x = jnp.ones((4, 50), jnp.int32)
        carry = model.init_carry(4)
        assert carry.dtype == jnp.bfloat16
        logits, carry2 = model.apply(params, x, carry=carry)
        assert carry2.dtype == jnp.bfloat16
    elif arch == "transformer":
        x = jnp.ones((4, 50), jnp.int32)
        logits = model.apply(params, x)
    else:
        x = jnp.ones_like(model.sample_input)
        logits = model.apply(params, x)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_bf16_gru_training_step_finite_and_f32_invariant():
    """One SGD step on the bf16 GRU: loss finite, updated params remain
    f32 (VERDICT r1 item 7 done-criteria)."""
    from fedtorch_tpu.core.losses import make_criterion

    model = define_model(_cfg_dtype("rnn", "shakespeare", "bfloat16"))
    params = model.init(jax.random.key(0))
    criterion = make_criterion(False)
    tokens = jax.random.randint(jax.random.key(1), (4, 50), 0, 86)
    targets = jax.random.randint(jax.random.key(2), (4, 50), 0, 86)

    def loss_fn(p):
        logits, _ = model.apply(p, tokens, carry=model.init_carry(4))
        return criterion(logits, targets)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    new_params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    for leaf in jax.tree.leaves(new_params):
        assert leaf.dtype == jnp.float32
    assert np.isfinite(float(loss_fn(new_params)))


def test_unknown_arch_raises():
    with pytest.raises(ValueError):
        define_model(_cfg("transformerXL", "mnist"))


# -- the conv zoo's parameter trees and lowering ------------------------
# tests/data/model_param_trees.json was written from the parent of PR 29
# (which removed the im2col lowering and its option): the same paths and
# shapes mean a checkpoint written before loads after.
_TREE_CASES = {
    "resnet20": ("resnet20", "cifar10", {}),
    "resnet56_bottleneck": ("resnet56", "cifar10", {}),
    "resnet18_imagenet": ("resnet18", "imagenet", {}),
    "wideresnet28": ("wideresnet28", "cifar10", {}),
    "densenet40": ("densenet40", "cifar10", {}),
    "densenet40_bc": ("densenet40", "cifar10",
                      {"densenet_bc_mode": True}),
    "cnn": ("cnn", "cifar10", {}),
}


@pytest.mark.parametrize("case", sorted(_TREE_CASES))
def test_conv_zoo_param_tree_and_lowering(case):
    from fedtorch_tpu.models import ModelDef, build_resnet

    arch, dataset, kw = _TREE_CASES[case]
    if dataset == "imagenet":
        # no loader names this family: the module alone, small images
        model = ModelDef(arch, build_resnet(arch, dataset),
                         jnp.zeros((2, 64, 64, 3)))
    else:
        model = define_model(_cfg(arch, dataset, **kw))
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "model_param_trees.json")) as f:
        want = json.load(f)[case]
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    got = {"/".join(str(k.key) for k in path): list(leaf.shape)
           for path, leaf in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == want
    # the one lowering, on the CPU as on the chip: a convolution for
    # every 4-D kernel, a product for every dense one and no other
    text = jax.jit(model.apply).lower(shapes, model.sample_input).as_text()
    for op, rank in (("stablehlo.convolution", 4),
                     ("stablehlo.dot_general", 2)):
        assert text.count(op) == sum(
            len(s) == rank for s in want.values()), op
