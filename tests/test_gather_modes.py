"""Gather-mode equivalence: 'batch' (move only the touched K*B rows) must
produce bit-identical training to 'shard' (move whole client shards)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FederatedConfig, ModelConfig, OptimConfig,
    TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer


def _build(gather_mode, algorithm="fedavg", **fed_kw):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=20,
                        batch_size=16, synthetic_alpha=0.5,
                        synthetic_beta=0.5),
        federated=FederatedConfig(federated=True, num_clients=8,
                                  online_client_rate=0.5,
                                  algorithm=algorithm,
                                  sync_type="local_step", **fed_kw),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.3, weight_decay=0.0),
        train=TrainConfig(local_step=5),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=16)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data.train,
                            val_data=data.val, gather_mode=gather_mode)


@pytest.mark.parametrize("algorithm,kw", [
    ("fedavg", {}),
    ("scaffold", {}),
    ("fedgate", {"compressed": True, "compressed_ratio": 1.0}),
    ("apfl", {}),
    ("apfl", {"adaptive_alpha": True}),  # pre_round hook equivalence
    ("perfedavg", {}),                   # val-stream equivalence
])
def test_batch_equals_shard(algorithm, kw):
    t_shard = _build("shard", algorithm, **kw)
    t_batch = _build("batch", algorithm, **kw)
    assert t_shard.gather_mode == "shard"
    assert t_batch.gather_mode == "batch"
    s1, c1 = t_shard.init_state(jax.random.key(3))
    s2, c2 = t_batch.init_state(jax.random.key(3))
    for _ in range(3):
        s1, c1, m1 = t_shard.run_round(s1, c1)
        s2, c2, m2 = t_batch.run_round(s2, c2)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m1.train_loss),
                                  np.asarray(m2.train_loss))


def test_auto_resolves_batch_default():
    t = _build("auto")
    assert t.gather_mode == "batch"


def test_auto_picks_shard_when_round_covers_shard():
    """Epoch-sync rounds revisit the whole shard (K*B >= n_max), where
    moving rows would inflate the footprint — auto must pick shard."""
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=20,
                        batch_size=16),
        federated=FederatedConfig(federated=True, num_clients=8,
                                  online_client_rate=0.5,
                                  algorithm="fedavg", sync_type="epoch",
                                  num_epochs_per_comm=2),
        model=ModelConfig(arch="logistic_regression"),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=16)
    t = FederatedTrainer(cfg, model, make_algorithm(cfg), data.train)
    assert t.local_steps * t.batch_size >= int(data.train.n_max)
    assert t.gather_mode == "shard"


def test_qffl_requires_shard():
    t = _build("auto", "qffl", qffl_q=1.0)
    assert t.gather_mode == "shard"
    with pytest.raises(ValueError, match="gather_mode"):
        _build("batch", "qffl", qffl_q=1.0)


def test_unknown_mode_rejected():
    with pytest.raises(ValueError, match="gather_mode"):
        _build("rows")


# -- the row gather itself (data/batching.gather_client_rows) --------------
# The round takes its cohort's rows one member's shard at a time; what
# it returns is held, bit for bit, to the one-gather spelling it
# replaced, which stays here as the reference.
def fancy_index_rows(stores, idx, rows):
    """``store[idx[:, None], rows]``: one gather whose operand is the
    whole store (the formulation before ``gather_client_rows``)."""
    return jax.tree.map(lambda s: s[idx[:, None], rows], stores)


def _raw(tree):
    """Leaves as bytes: equal bit patterns, NaNs and signed zeros
    included (typed PRNG keys by their data)."""
    out = []
    for leaf in jax.tree.leaves(tree):
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        out.append(np.asarray(leaf).tobytes())
    return out


def _store(kind, C=6, n_max=11):
    rng = np.random.RandomState(7)
    shape = {"image": (C, n_max, 8, 8, 3), "features": (C, n_max, 20),
             "labels": (C, n_max), "bf16": (C, n_max, 5)}[kind]
    if kind == "labels":
        return jnp.asarray(rng.randint(0, 10, shape), jnp.int32)
    x = rng.randn(*shape).astype(np.float32)
    # bit patterns a float-typed move could disturb
    x.reshape(-1)[:4] = [np.nan, -0.0, np.inf, 1e-42]
    return jnp.asarray(x, jnp.bfloat16 if kind == "bf16" else jnp.float32)


@pytest.mark.parametrize("kind", ["image", "features", "labels", "bf16"])
def test_gather_client_rows_is_the_fancy_index_bitwise(kind):
    from fedtorch_tpu.data.batching import gather_client_rows
    store = _store(kind)
    # repeated and out-of-order clients; rows repeated, out of order and
    # reaching into the cyclic padding tail (any row < n_max is stored)
    idx = jnp.asarray([4, 0, 4, 5, 2], jnp.int32)
    rows = jnp.asarray(np.random.RandomState(3).randint(
        0, store.shape[1], (5, 9)), jnp.int32).at[:, -1].set(
        store.shape[1] - 1).at[0, :3].set(0)
    got = jax.jit(gather_client_rows)(store, idx, rows)
    want = jax.jit(fancy_index_rows)(store, idx, rows)
    assert got.shape == rows.shape + store.shape[2:]
    assert got.dtype == store.dtype
    assert _raw(got) == _raw(want)
    # a pytree of stores goes through one loop, leaf for leaf
    both = jax.jit(gather_client_rows)((store, store), idx, rows)
    assert _raw(both) == _raw((want, want))


def _image_trainer():
    """CIFAR-shaped CNN, flip-and-crop on, bf16 compute, unequal shards
    (short clients' rows come from the padding tail)."""
    from fedtorch_tpu.config import MeshConfig
    from fedtorch_tpu.data.batching import stack_partitions
    sizes = (24, 9, 17, 24)
    cfg = ExperimentConfig(
        data=DataConfig(dataset="cifar10", batch_size=6, augment=True),
        federated=FederatedConfig(
            federated=True, num_clients=len(sizes),
            online_client_rate=0.5, algorithm="fedavg",
            sync_type="local_step"),
        model=ModelConfig(arch="cnn", norm="bn"),
        optim=OptimConfig(lr=0.05),
        train=TrainConfig(local_step=2),
        mesh=MeshConfig(num_devices=1, compute_dtype="bfloat16"),
    ).finalize()
    rng = np.random.RandomState(0)
    feats = rng.randn(sum(sizes), 32, 32, 3).astype(np.float32)
    labels = rng.randint(0, 10, sum(sizes))
    off = np.concatenate([[0], np.cumsum(sizes)])
    parts = [np.arange(off[i], off[i + 1]) for i in range(len(sizes))]
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    return FederatedTrainer(cfg, model, make_algorithm(cfg),
                            stack_partitions(feats, labels, parts))


def _mlp_trainer():
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=20,
                        batch_size=8),
        federated=FederatedConfig(federated=True, num_clients=8,
                                  online_client_rate=0.5,
                                  algorithm="fedavg",
                                  sync_type="local_step"),
        model=ModelConfig(arch="mlp"),
        optim=OptimConfig(lr=0.1),
        train=TrainConfig(local_step=3),
    ).finalize()
    data = build_federated_data(cfg)
    return FederatedTrainer(cfg, define_model(cfg, batch_size=8),
                            make_algorithm(cfg), data.train)


def _commit_trainer():
    """The async plane's resident commit program (its own two calls of
    the helper, parallel/round_program.py)."""
    from fedtorch_tpu.async_plane import AsyncFederatedTrainer
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=10,
                        batch_size=8),
        federated=FederatedConfig(
            federated=True, num_clients=12, num_comms=4,
            online_client_rate=0.5, algorithm="fedavg",
            sync_type="local_step", sync_mode="async"),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.5, weight_decay=0.0),
        train=TrainConfig(local_step=2),
    ).finalize()
    data = build_federated_data(cfg)
    return AsyncFederatedTrainer(
        cfg, define_model(cfg, batch_size=8), make_algorithm(cfg),
        data.train)


ROUND_CELLS = {
    "fedavg-augment-bf16": _image_trainer,
    # live pre_round rows (adaptive alpha) and validation rows
    "apfl": lambda: _build("batch", "apfl", adaptive_alpha=True),
    "perfedavg-val-rows": lambda: _build("batch", "perfedavg"),
    "mlp": _mlp_trainer,
    "async-resident-commit": _commit_trainer,
}


@pytest.mark.parametrize("cell", sorted(ROUND_CELLS))
def test_round_is_bitwise_the_fancy_index_round(cell, monkeypatch):
    """Whole rounds (state, client state and metrics) with the helper
    against the same rounds with the one-gather spelling in its place."""
    from fedtorch_tpu.parallel import federated, round_program

    def rounds(trainer):
        assert trainer.gather_mode == "batch"
        state = trainer.init_state(jax.random.key(5))
        out = []
        for _ in range(2):
            *state, metrics = trainer.run_round(*state)
            out.append(_raw((state, metrics)))
        trainer.invalidate_stream()
        return out

    new = rounds(ROUND_CELLS[cell]())
    for module in (federated, round_program):
        monkeypatch.setattr(module, "gather_client_rows", fancy_index_rows)
    assert rounds(ROUND_CELLS[cell]()) == new


def test_round_program_takes_whole_shards_off_the_store_and_nothing_else():
    """The lowered ResNet-20 round: no convert whose operand or result
    has the store's C x n_max x 3072 elements, and the only gathers
    with the store as operand take ONE whole shard off the leading axis
    (a slice where the client axis is on one device, and what the
    partitioner can serve from the shard's owner where it is split; a
    ``dynamic_slice`` there makes it all-gather the store). The rows
    are gathered from that shard's flat view. What the TPU compiler
    then makes of it only the chip's trace says; the one-gather
    spelling fails here on its slice sizes."""
    import re

    from fedtorch_tpu.config import MeshConfig
    from fedtorch_tpu.data.batching import ClientData
    C, n_max = 5, 23
    cfg = ExperimentConfig(
        data=DataConfig(dataset="cifar10", batch_size=4, augment=True),
        federated=FederatedConfig(
            federated=True, num_clients=C, online_client_rate=0.4,
            algorithm="fedavg", sync_type="local_step"),
        model=ModelConfig(arch="resnet20"),
        optim=OptimConfig(lr=0.1),
        train=TrainConfig(local_step=2),
        mesh=MeshConfig(num_devices=1, compute_dtype="bfloat16"),
    ).finalize()
    store = ClientData(x=np.zeros((C, n_max, 32, 32, 3), np.float32),
                       y=np.zeros((C, n_max), np.int32),
                       sizes=np.full((C,), n_max, np.int32))
    trainer = FederatedTrainer(cfg, define_model(cfg, batch_size=4),
                               make_algorithm(cfg), store)
    assert trainer.gather_mode == "batch"
    server, clients = jax.eval_shape(trainer.init_state, jax.random.key(0))
    text = jax.jit(trainer.round_fn).lower(
        server, clients, trainer.data).as_text()
    store_elems = C * n_max * 32 * 32 * 3
    whole_shard = f"slice_sizes = array<i64: 1, {n_max}, 32, 32, 3>"
    shard_gathers = row_gathers = 0
    for line in text.splitlines():
        gather = "stablehlo.gather" in line
        if not gather and "stablehlo.convert" not in line:
            continue
        sizes = [int(np.prod([int(d) for d in dims.split("x") if d]))
                 for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", line)]
        if store_elems in sizes:
            assert gather and whole_shard in line, line[:300]
            shard_gathers += 1
        row_gathers += gather and f"tensor<{n_max}x3072xui32>" in line
    # one loop body: the train rows' (pre_round's rows are dead code
    # under FedAvg and dropped before lowering)
    assert shard_gathers == row_gathers == 1
