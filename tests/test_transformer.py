"""Transformer LM: causal correctness, federated training, and the
sequence-parallel long-context path."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FederatedConfig, ModelConfig, OptimConfig,
    TrainConfig,
)
from fedtorch_tpu.models import define_model
from fedtorch_tpu.models.transformer import TransformerLM, \
    long_context_apply


def _model(seq_len=32):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="shakespeare"),
        model=ModelConfig(arch="transformer", rnn_seq_len=seq_len,
                          rnn_hidden_size=32, mlp_num_layers=2,
                          vocab_size=86))
    return define_model(cfg, batch_size=4)


def test_shapes_and_causality():
    model = _model()
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, 86)
    logits = model.apply(params, toks)
    assert logits.shape == (4, 32, 86)
    # causality: changing a future token must not affect earlier logits
    toks2 = toks.at[:, 20].set((toks[:, 20] + 1) % 86)
    logits2 = model.apply(params, toks2)
    np.testing.assert_allclose(np.asarray(logits[:, :20]),
                               np.asarray(logits2[:, :20]), atol=1e-5)
    assert not np.allclose(np.asarray(logits[:, 20:]),
                           np.asarray(logits2[:, 20:]))


def test_federated_training_converges():
    """Char-LM on a repetitive corpus: loss must drop fast."""
    cfg = ExperimentConfig(
        data=DataConfig(dataset="shakespeare", batch_size=8),
        federated=FederatedConfig(federated=True, num_clients=4,
                                  num_comms=10, online_client_rate=1.0,
                                  algorithm="fedavg",
                                  sync_type="local_step"),
        model=ModelConfig(arch="transformer", rnn_seq_len=16,
                          rnn_hidden_size=16, mlp_num_layers=1,
                          vocab_size=86),
        optim=OptimConfig(lr=0.05, weight_decay=0.0),
        train=TrainConfig(local_step=5),
    ).finalize()
    model = define_model(cfg, batch_size=8)
    # synthetic periodic char stream (period 4 -> highly learnable)
    rng = np.random.RandomState(0)
    stream = np.tile(np.asarray([5, 17, 42, 63]), 600)
    n_win = (len(stream) - 1) // 16
    x = stream[:n_win * 16].reshape(n_win, 16)
    y = stream[1:n_win * 16 + 1].reshape(n_win, 16)
    from fedtorch_tpu.data.batching import stack_partitions
    parts = np.array_split(rng.permutation(n_win), 4)
    data = stack_partitions(x, y, parts)
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.parallel import FederatedTrainer
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
    server, clients = trainer.init_state(jax.random.key(0))
    first = None
    for _ in range(10):
        server, clients, m = trainer.run_round(server, clients)
        loss = float(jnp.sum(m.train_loss) / 4)
        if first is None:
            first = loss
    assert loss < first * 0.5, (first, loss)


@pytest.mark.parametrize("algorithm,fed_kw", [
    ("scaffold", {}),
    ("fedgate", {"compressed": True, "compressed_ratio": 0.5}),
    ("qsparse", {"compressed": True, "compressed_ratio": 0.5}),
    ("apfl", {"personal": True}),
    # the two hardest hooks: DRFA's two-phase round (kth-model snapshot
    # + second sampling + dual update) and qFFL's full-data loss pass
    ("fedavg", {"drfa": True, "drfa_gamma": 0.1,
                "online_client_rate": 0.5}),
    ("qffl", {"qffl_q": 1.0}),
])
def test_algorithm_zoo_composes_with_transformer(algorithm, fed_kw):
    """The aggregation families are pytree-generic: control variates,
    top-k wire formats, and personal models must run unchanged on the
    transformer (incl. a sparse-MoE variant), not just the MLP the
    dryrun matrix uses. One round each, finite loss."""
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.parallel import FederatedTrainer

    rng = np.random.RandomState(1)
    x = rng.randint(0, 86, (32, 16)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    parts = [np.arange(i * 8, (i + 1) * 8) for i in range(4)]
    data = stack_partitions(x, y, parts)
    cfg = ExperimentConfig(
        data=DataConfig(dataset="shakespeare", batch_size=4),
        federated=FederatedConfig(**{
            "federated": True, "num_clients": 4,
            "online_client_rate": 1.0, "algorithm": algorithm,
            "sync_type": "local_step", **fed_kw}),
        model=ModelConfig(arch="transformer", rnn_seq_len=16,
                          rnn_hidden_size=8, mlp_num_layers=1,
                          moe_experts=2, moe_capacity_factor=1.5),
        optim=OptimConfig(lr=0.05, weight_decay=0.0),
        train=TrainConfig(local_step=2),
    ).finalize()
    model = define_model(cfg, batch_size=4)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
    server, clients = trainer.init_state(jax.random.key(0))
    _, _, m = trainer.run_round(server, clients)
    loss = float(m.train_loss.sum() / m.online_mask.sum())
    assert np.isfinite(loss)


def test_long_context_ring_matches_dense():
    """The ring-attention forward must equal the dense forward."""
    model = _model(seq_len=64)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(2), (2, 64), 0, 86)
    dense = model.apply(params, toks)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("sp",))
    ring = long_context_apply(model.module, params, toks, mesh)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               atol=3e-4, rtol=3e-4)


def test_large_e_dense_dispatch_warns():
    """E>=8 with dense dispatch is oracle mode at Ex the FLOPs; the
    factory nudges toward sparse dispatch."""
    import warnings

    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, ModelConfig,
    )
    from fedtorch_tpu.models import define_model
    cfg = ExperimentConfig(
        data=DataConfig(dataset="shakespeare", batch_size=2),
        model=ModelConfig(arch="transformer", moe_experts=8)).finalize()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        define_model(cfg, batch_size=2)
    assert any("moe_capacity_factor" in str(x.message) for x in w)
    # sparse dispatch silences it
    cfg2 = ExperimentConfig(
        data=DataConfig(dataset="shakespeare", batch_size=2),
        model=ModelConfig(arch="transformer", moe_experts=8,
                          moe_capacity_factor=1.25)).finalize()
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        define_model(cfg2, batch_size=2)
    assert not any("moe_capacity_factor" in str(x.message) for x in w2)
