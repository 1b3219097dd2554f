"""Federated engine tests: hand-computed aggregation, convergence smoke
tests (SURVEY.md §4 requirements a & d), determinism, and participation
semantics."""
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
from fedtorch_tpu.parallel import FederatedTrainer, evaluate
from fedtorch_tpu.parallel.federated import participation_indices


def make_trainer(algorithm="fedavg", num_clients=8, rate=1.0, lr=0.1,
                 local_step=5, dataset="synthetic", arch="logistic_regression",
                 mesh_kw=None, **fed_kw):
    from fedtorch_tpu.config import MeshConfig
    cfg = ExperimentConfig(
        data=DataConfig(dataset=dataset, synthetic_dim=20, batch_size=32,
                        synthetic_alpha=0.5, synthetic_beta=0.5),
        federated=FederatedConfig(
            federated=True, num_clients=num_clients, num_comms=20,
            online_client_rate=rate, algorithm=algorithm,
            sync_type="local_step", **fed_kw),
        model=ModelConfig(arch=arch),
        optim=OptimConfig(lr=lr, weight_decay=0.0),
        train=TrainConfig(local_step=local_step),
        mesh=MeshConfig(**(mesh_kw or {})),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    alg = make_algorithm(cfg)
    return FederatedTrainer(cfg, model, alg, data.train), data, cfg


class TestParticipation:
    def test_round0_forces_client0(self):
        for seed in range(5):
            idx = participation_indices(jax.random.key(seed), 10, 3,
                                        jnp.asarray(0))
            assert 0 in np.asarray(idx)

    def test_later_rounds_uniform(self):
        seen = set()
        for seed in range(20):
            idx = participation_indices(jax.random.key(seed), 10, 3,
                                        jnp.asarray(5))
            arr = np.asarray(idx)
            assert len(np.unique(arr)) == 3
            seen.update(arr.tolist())
        assert len(seen) == 10  # every client eventually sampled


class TestFedAvgAggregation:
    def test_one_round_hand_computed(self):
        """Full participation, 1 local step, lr known -> the server update
        equals the average client delta (fedavg.py semantics)."""
        trainer, data, cfg = make_trainer(num_clients=4, rate=1.0,
                                          local_step=1, lr=0.1)
        server, clients = trainer.init_state(jax.random.key(0))
        s0 = jax.tree.map(np.asarray, server.params)

        server2, clients2, metrics = trainer.run_round(server, clients)

        # reconstruct: every client does one SGD step from s0 on its own
        # batch; delta_i = s0 - x_i = lr * g_i; server p = s0 - mean(delta)
        new_clients_params = jax.tree.map(np.asarray, clients2.params)
        # all clients end the round holding the server model
        for leaf in jax.tree.leaves(new_clients_params):
            for c in range(1, 4):
                np.testing.assert_allclose(leaf[c], leaf[0], atol=1e-6)
        s2 = jax.tree.map(np.asarray, server2.params)
        # server changed
        assert any(np.abs(a - b).max() > 0
                   for a, b in zip(jax.tree.leaves(s0),
                                   jax.tree.leaves(s2)))

    def test_weights_sum_to_one_with_client0(self):
        """Regression test: weights must sum to 1 when client 0 is online
        (reference rank_weight rule, fedavg.py:18-27) — a double
        normalization once silently halved every server update."""
        cfg = ExperimentConfig(federated=FederatedConfig(
            federated=True, algorithm="fedavg")).finalize()
        alg = make_algorithm(cfg)
        idx = jnp.asarray([0, 3, 5, 7])
        w = alg.client_weights((), idx, 4.0, jnp.ones(4))
        assert float(jnp.sum(w)) == pytest.approx(1.0)
        # client 0 offline: denominator is k+1 (rank-0 server quirk)
        w2 = alg.client_weights((), jnp.asarray([2, 3, 5, 7]), 5.0,
                                jnp.ones(4))
        assert float(jnp.sum(w2)) == pytest.approx(4.0 / 5.0)

    def test_weighted_sum_matches_manual(self):
        """Drive the algorithm object directly with synthetic deltas."""
        cfg = ExperimentConfig(federated=FederatedConfig(
            federated=True, algorithm="fedavg")).finalize()
        alg = make_algorithm(cfg)
        delta = {"w": jnp.asarray([1.0, 2.0])}
        payload, _ = alg.client_payload(
            delta=delta, client_aux=(), params=None, server_params=None,
            server_aux=(), lr=0.1, local_steps=5,
            weight=jnp.asarray(0.25))
        np.testing.assert_allclose(np.asarray(payload["w"]), [0.25, 0.5])


class TestConvergence:
    def test_fedavg_logistic_converges(self):
        trainer, data, cfg = make_trainer(num_clients=8, rate=1.0,
                                          local_step=5, lr=0.5)
        server, clients = trainer.init_state(jax.random.key(1))
        first_loss = None
        for r in range(15):
            server, clients, metrics = trainer.run_round(server, clients)
            loss = float(jnp.sum(metrics.train_loss)
                         / jnp.maximum(jnp.sum(metrics.online_mask), 1))
            if first_loss is None:
                first_loss = loss
        res = evaluate(trainer.model, server.params, data.test_x,
                       data.test_y, batch_size=128)
        assert loss < first_loss * 0.8, (first_loss, loss)
        assert float(res.top1) > 0.5

    def test_partial_participation_converges(self):
        trainer, data, cfg = make_trainer(num_clients=8, rate=0.5,
                                          local_step=5, lr=0.5)
        server, clients = trainer.init_state(jax.random.key(2))
        for r in range(20):
            server, clients, metrics = trainer.run_round(server, clients)
            assert float(jnp.sum(metrics.online_mask)) == 4.0
        res = evaluate(trainer.model, server.params, data.test_x,
                       data.test_y, batch_size=128)
        assert float(res.top1) > 0.5

    def test_fedprox_converges(self):
        trainer, data, cfg = make_trainer(algorithm="fedprox",
                                          num_clients=8, rate=1.0,
                                          local_step=5, lr=0.5)
        server, clients = trainer.init_state(jax.random.key(3))
        for r in range(15):
            server, clients, _ = trainer.run_round(server, clients)
        res = evaluate(trainer.model, server.params, data.test_x,
                       data.test_y, batch_size=128)
        assert float(res.top1) > 0.5

    def test_fedadam_converges(self):
        trainer, data, cfg = make_trainer(algorithm="fedadam",
                                          num_clients=8, rate=1.0,
                                          local_step=5, lr=0.5,
                                          fedadam_tau=0.1)
        server, clients = trainer.init_state(jax.random.key(4))
        for r in range(15):
            server, clients, _ = trainer.run_round(server, clients)
        res = evaluate(trainer.model, server.params, data.test_x,
                       data.test_y, batch_size=128)
        assert float(res.top1) > 0.5

    def test_quantized_fedavg_converges(self):
        trainer, data, cfg = make_trainer(num_clients=8, rate=1.0,
                                          local_step=5, lr=0.5,
                                          quantized=True, quantized_bits=8)
        server, clients = trainer.init_state(jax.random.key(5))
        for r in range(15):
            server, clients, _ = trainer.run_round(server, clients)
        res = evaluate(trainer.model, server.params, data.test_x,
                       data.test_y, batch_size=128)
        assert float(res.top1) > 0.5


class TestDeterminism:
    def test_same_seed_same_result(self):
        t1, _, _ = make_trainer(num_clients=4, rate=0.5)
        s1, c1 = t1.init_state(jax.random.key(7))
        s2, c2 = t1.init_state(jax.random.key(7))
        s1, c1, _ = t1.run_round(s1, c1)
        s2, c2, _ = t1.run_round(s2, c2)
        for a, b in zip(jax.tree.leaves(s1.params),
                        jax.tree.leaves(s2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestBatchedRounds:
    def test_run_rounds_matches_run_round(self):
        """run_rounds (N rounds in one lax.scan device call, the bench
        fast path) must reproduce N sequential run_round calls exactly:
        same server params, same client state, same per-round metrics."""
        trainer, _, _ = make_trainer(rate=0.5, local_step=3)
        s1, c1 = trainer.init_state(jax.random.key(0))
        s2, c2 = trainer.init_state(jax.random.key(0))
        seq_metrics = []
        for _ in range(3):
            s1, c1, m = trainer.run_round(s1, c1)
            seq_metrics.append(m)
        s2, c2, ms = trainer.run_rounds(s2, c2, 3)
        for a, b in zip(jax.tree.leaves(s1.params),
                        jax.tree.leaves(s2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
        for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
        for r in range(3):
            np.testing.assert_allclose(
                np.asarray(ms.train_loss[r]),
                np.asarray(seq_metrics[r].train_loss), atol=1e-6)
            np.testing.assert_array_equal(
                np.asarray(ms.online_mask[r]),
                np.asarray(seq_metrics[r].online_mask))

    def test_run_rounds_on_sharded_mesh(self):
        """The scanned driver composes with the sharded client axis."""
        trainer, _, _ = make_trainer(mesh_kw={"num_devices": 8})
        s, c = trainer.init_state(jax.random.key(1))
        s, c, ms = trainer.run_rounds(s, c, 2)
        loss = np.asarray(ms.train_loss.sum(-1) / ms.online_mask.sum(-1))
        assert loss.shape == (2,) and np.all(np.isfinite(loss))


class TestMLPEngine:
    def test_mlp_round_runs(self):
        trainer, data, cfg = make_trainer(arch="mlp", num_clients=4,
                                          rate=1.0, local_step=2, lr=0.1)
        server, clients = trainer.init_state(jax.random.key(0))
        server, clients, metrics = trainer.run_round(server, clients)
        assert np.isfinite(float(jnp.sum(metrics.train_loss)))


class TestAsyncGateMatrix:
    """ISSUE 6 satellite: every unsupported combination of
    `--sync_mode async` must raise ONE clear ValueError naming the
    gate at construction (the stream-plane gate style) — never fail
    deep in tracing."""

    def _async_cfg(self, algorithm="fedavg", num_clients=12, rate=0.5,
                   mesh_kw=None, **fed_kw):
        from fedtorch_tpu.config import MeshConfig
        return ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=20,
                            batch_size=32, synthetic_alpha=0.5,
                            synthetic_beta=0.5),
            federated=FederatedConfig(
                federated=True, num_clients=num_clients, num_comms=4,
                online_client_rate=rate, algorithm=algorithm,
                sync_type="local_step", sync_mode="async", **fed_kw),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.1, weight_decay=0.0),
            train=TrainConfig(local_step=2),
            mesh=MeshConfig(**(mesh_kw or {})),
        ).finalize()

    def _build(self, cfg, **kw):
        from fedtorch_tpu.async_plane import AsyncFederatedTrainer
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=cfg.data.batch_size)
        return AsyncFederatedTrainer(cfg, model, make_algorithm(cfg),
                                     data.train, **kw)

    @pytest.mark.parametrize("algorithm", [
        "fedgate", "afl", "qffl", "qsparse", "apfl", "perfedme",
        "perfedavg"])
    def test_gated_algorithms_raise_named_gate(self, algorithm):
        cfg = self._async_cfg(algorithm=algorithm)
        with pytest.raises(ValueError,
                           match="sync_mode='async' is unsupported"):
            self._build(cfg)

    def test_drfa_wrapper_gated(self):
        cfg = self._async_cfg(algorithm="fedavg", drfa=True)
        with pytest.raises(ValueError, match="drfa"):
            self._build(cfg)

    @pytest.mark.parametrize("algorithm", [
        "fedavg", "fedprox", "fedadam", "scaffold"])
    def test_supported_algorithms_construct(self, algorithm):
        cfg = self._async_cfg(algorithm=algorithm)
        self._build(cfg)  # must not raise

    def test_sequential_client_fusion_gated(self):
        cfg = self._async_cfg(mesh_kw={"client_fusion": "sequential",
                                       "num_devices": 1})
        with pytest.raises(ValueError, match="client_fusion"):
            self._build(cfg)

    def test_shard_gather_gated(self):
        cfg = self._async_cfg()
        with pytest.raises(ValueError, match="shard"):
            self._build(cfg, gather_mode="shard")

    def test_buffer_exceeding_concurrency_gated(self):
        cfg = self._async_cfg(async_buffer_size=5, async_concurrency=2)
        with pytest.raises(ValueError, match="async_buffer_size"):
            self._build(cfg)

    def test_too_small_population_gated(self):
        cfg = self._async_cfg(num_clients=6, rate=1.0)
        with pytest.raises(ValueError, match="num_clients"):
            self._build(cfg)

    def test_base_trainer_refuses_async_config(self):
        cfg = self._async_cfg()
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=cfg.data.batch_size)
        with pytest.raises(ValueError, match="AsyncFederatedTrainer"):
            FederatedTrainer(cfg, model, make_algorithm(cfg),
                             data.train)

    def test_run_rounds_refused_on_async_plane(self):
        trainer = self._build(self._async_cfg())
        server, clients = trainer.init_state(jax.random.key(0))
        with pytest.raises(ValueError, match="run_rounds"):
            trainer.run_rounds(server, clients, 2)
