"""MoE layer + expert parallelism (models/transformer.MoEMLP,
parallel/expert.py)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from fedtorch_tpu.models.transformer import (
    MoEMLP, TransformerLM, routing_fractions,
)
from fedtorch_tpu.parallel.expert import ep_moe_apply


def _layer(E=8, d=16, B=2, T=12):
    layer = MoEMLP(num_experts=E)
    x = jax.random.normal(jax.random.key(1), (B, T, d))
    params = layer.init(jax.random.key(0), x)["params"]
    return layer, params, x


class TestMoELayer:
    def test_tokens_route_to_argmax_expert(self):
        """Each token's output must equal its top-1 expert's MLP output
        scaled by the gate probability (capacity = all tokens, exact)."""
        layer, params, x = _layer(E=4)
        out = layer.apply({"params": params}, x)
        logits = x.astype(jnp.float32) @ params["gate"]["kernel"]
        probs = jax.nn.softmax(logits, axis=-1)
        sel = np.asarray(jnp.argmax(probs, axis=-1))
        for b in range(x.shape[0]):
            for t in range(x.shape[1]):
                e = sel[b, t]
                h = jax.nn.gelu(x[b, t] @ params["w_in"][e]
                                + params["b_in"][e])
                y = (h @ params["w_out"][e] + params["b_out"][e]) \
                    * probs[b, t, e]
                np.testing.assert_allclose(np.asarray(out[b, t]),
                                           np.asarray(y), atol=1e-5)

    def test_moe_transformer_forward(self):
        model = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                              num_layers=2, max_len=16, num_experts=4)
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 32)
        params = model.init(jax.random.key(0), toks)["params"]
        out = model.apply({"params": params}, toks)
        assert out.shape == (2, 16, 32)
        assert bool(jnp.all(jnp.isfinite(out)))
        assert "moe" in params["block_0"]


class TestSparseDispatch:
    """capacity_factor > 0: gather/scatter Switch dispatch
    (transformer.py moe_sparse_compute)."""

    def test_ample_capacity_equals_dense(self):
        """With capacity >= tokens-per-expert no token drops, so the
        sparse path must reproduce the dense one-hot dispatch exactly
        (same per-token expert MLP math, different data movement)."""
        layer, params, x = _layer(E=4)
        dense = layer.apply({"params": params}, x)
        sparse = MoEMLP(num_experts=4, capacity_factor=4.0).apply(
            {"params": params}, x)
        np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                                   atol=1e-6)

    def test_over_capacity_tokens_drop_to_zero(self):
        """Switch §2.2: tokens past an expert's capacity contribute 0
        from the MoE branch (the block's residual passes them through).
        Force every token onto expert 0 via the gate kernel; with
        capacity C only the first C tokens (storage order) survive."""
        layer, params, x = _layer(E=4, B=1, T=8)
        params = dict(params)
        gate_k = np.zeros_like(np.asarray(params["gate"]["kernel"]))
        gate_k[:, 0] = 0.0  # uniform logits -> argmax = expert 0
        params["gate"] = {"kernel": jnp.asarray(gate_k)}
        # capacity_factor 1.0 -> C = ceil(8/4) = 2 per expert
        out = MoEMLP(num_experts=4, capacity_factor=1.0).apply(
            {"params": params}, x)
        out = np.asarray(out[0])
        assert np.abs(out[:2]).max() > 0  # first 2 tokens computed
        np.testing.assert_array_equal(out[2:], 0.0)  # rest dropped

    def test_dropped_tokens_pass_residual_in_block(self):
        """In a full MoE transformer the dropped token's block output
        equals its residual input (plus attention)."""
        model = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                              num_layers=1, max_len=16, num_experts=4,
                              capacity_factor=0.25)
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 32)
        params = model.init(jax.random.key(0), toks)["params"]
        out = model.apply({"params": params}, toks)
        assert bool(jnp.all(jnp.isfinite(out)))


class TestAuxLoss:
    """Switch load-balance aux loss (arXiv:2101.03961 §2.2) + routing
    fraction metrics."""

    def test_uniform_routing_gives_one(self):
        """aux = E * sum_e f_e P_e -> 1 under perfectly uniform routing;
        near-1 for random gates over random tokens."""
        layer, params, x = _layer(E=4, B=4, T=32)
        _, var = layer.apply({"params": params}, x,
                             mutable=["aux_loss"])
        aux = float(var["aux_loss"]["load_balance"][0])
        assert 0.9 < aux < 1.5

    def test_collapsed_routing_approaches_E(self):
        """All tokens on one expert -> f = P ~ onehot -> aux ~ E."""
        E = 4
        layer, params, x = _layer(E=E)
        x = jnp.abs(x) + 0.1            # positive tokens, so that a
        gate_k = np.zeros((x.shape[-1], E), np.float32)
        gate_k[:, 0] = 10.0             # +col-0 kernel always wins
        params = dict(params)
        params["gate"] = {"kernel": jnp.asarray(gate_k)}
        _, var = layer.apply({"params": params}, x,
                             mutable=["aux_loss"])
        aux = float(var["aux_loss"]["load_balance"][0])
        assert aux > 0.9 * E

    def test_aux_loss_is_differentiable_toward_balance(self):
        """The gate gradient of the aux loss must push away from the
        overloaded expert (that is its whole job)."""
        layer, params, x = _layer(E=4)

        def aux_of(p):
            _, var = layer.apply({"params": p}, x, mutable=["aux_loss"])
            return var["aux_loss"]["load_balance"][0]

        g = jax.grad(aux_of)(params)
        assert float(jnp.max(jnp.abs(g["gate"]["kernel"]))) > 0

    def test_routing_fractions_metric(self):
        model = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                              num_layers=2, max_len=16, num_experts=4)
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 32)
        params = model.init(jax.random.key(0), toks)["params"]
        fr = routing_fractions(model, params, toks)
        assert set(fr) == {"block_0", "block_1"}
        for f in fr.values():
            assert f.shape == (4,)
            np.testing.assert_allclose(float(f.sum()), 1.0, atol=1e-5)

    def test_drop_fractions_metric(self):
        """drop_fractions: 0 at ample capacity, >0 at a tight one."""
        from fedtorch_tpu.models.transformer import drop_fractions
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 32)
        ample = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                              num_layers=1, max_len=16, num_experts=4,
                              capacity_factor=4.0)
        params = ample.init(jax.random.key(0), toks)["params"]
        df = drop_fractions(ample, params, toks)
        assert set(df) == {"block_0"}
        assert float(df["block_0"]) == 0.0
        tight = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                              num_layers=1, max_len=16, num_experts=4,
                              capacity_factor=0.25)
        df = drop_fractions(tight, params, toks)
        assert float(df["block_0"]) > 0.0
        # exact dense dispatch sows no drop stat
        dense = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                              num_layers=1, max_len=16, num_experts=4)
        assert drop_fractions(dense, params, toks) == {}

    def test_dense_models_sow_nothing(self):
        model = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                              num_layers=1, max_len=16)
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 32)
        params = model.init(jax.random.key(0), toks)["params"]
        assert routing_fractions(model, params, toks) == {}


class TestExpertParallel:
    @pytest.mark.parametrize("n_ep", [1, 2, 4, 8])
    def test_matches_single_device(self, n_ep):
        layer, params, x = _layer(E=8)
        dense = layer.apply({"params": params}, x)
        mesh = Mesh(np.asarray(jax.devices()[:n_ep]), ("ep",))
        out = ep_moe_apply(params, x, mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("n_ep", [2, 8])
    def test_sparse_dispatch_matches_dense(self, n_ep):
        """EP sparse path (per-device token gather over the expert
        shard) == single-device dense output at ample capacity."""
        layer, params, x = _layer(E=8)
        dense = layer.apply({"params": params}, x)
        mesh = Mesh(np.asarray(jax.devices()[:n_ep]), ("ep",))
        out = ep_moe_apply(params, x, mesh, capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=2e-5, rtol=2e-5)

    def test_sparse_dispatch_matches_module_sparse_with_drops(self):
        """With a TIGHT capacity the EP sparse path must drop exactly
        the tokens the single-device sparse module drops."""
        layer, params, x = _layer(E=8, B=2, T=12)
        cf = 0.5
        ref = MoEMLP(num_experts=8, capacity_factor=cf).apply(
            {"params": params}, x)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("ep",))
        out = ep_moe_apply(params, x, mesh, capacity_factor=cf)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_rejects_indivisible_experts(self):
        layer, params, x = _layer(E=6)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("ep",))
        with pytest.raises(ValueError, match="divisible"):
            ep_moe_apply(params, x, mesh)


def test_federated_moe_via_config_surface():
    """moe_experts threads from ModelConfig through define_model into a
    federated round (the CLI path)."""
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer

    rng = np.random.RandomState(3)
    x = rng.randint(0, 86, (32, 16)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    parts = [np.arange(i * 8, (i + 1) * 8) for i in range(4)]
    data = stack_partitions(x, y, parts)
    cfg = ExperimentConfig(
        data=DataConfig(dataset="shakespeare", batch_size=4),
        federated=FederatedConfig(
            federated=True, num_clients=4, online_client_rate=1.0,
            algorithm="fedavg", sync_type="local_step"),
        model=ModelConfig(arch="transformer", mlp_num_layers=1,
                          rnn_seq_len=16, rnn_hidden_size=8,
                          moe_experts=2),
        optim=OptimConfig(lr=0.05, weight_decay=0.0),
        train=TrainConfig(local_step=2),
        mesh=MeshConfig(num_devices=1),
    ).finalize()
    model = define_model(cfg, batch_size=4)
    assert "moe" in model.init(jax.random.key(0))["block_0"]
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
    server, clients = trainer.init_state(jax.random.key(0))
    server, clients, m = trainer.run_round(server, clients)
    loss = float(m.train_loss.sum() / m.online_mask.sum())
    assert np.isfinite(loss)


def test_federated_moe_sparse_with_aux_loss():
    """Sparse dispatch + Switch aux loss thread through the engine: the
    aux term must actually enter the training loss (losses with weight
    on differ from weight off) and stay finite."""
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer

    rng = np.random.RandomState(3)
    x = rng.randint(0, 86, (32, 16)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    parts = [np.arange(i * 8, (i + 1) * 8) for i in range(4)]
    data = stack_partitions(x, y, parts)

    def run(aux_w):
        cfg = ExperimentConfig(
            data=DataConfig(dataset="shakespeare", batch_size=4),
            federated=FederatedConfig(
                federated=True, num_clients=4, online_client_rate=1.0,
                algorithm="fedavg", sync_type="local_step"),
            model=ModelConfig(arch="transformer", mlp_num_layers=1,
                              rnn_seq_len=16, rnn_hidden_size=8,
                              moe_experts=2, moe_capacity_factor=1.5,
                              moe_aux_weight=aux_w),
            optim=OptimConfig(lr=0.05, weight_decay=0.0),
            train=TrainConfig(local_step=2),
            mesh=MeshConfig(num_devices=1),
        ).finalize()
        model = define_model(cfg, batch_size=4)
        assert model.has_aux_loss
        trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
        server, clients = trainer.init_state(jax.random.key(0))
        _, _, m = trainer.run_round(server, clients)
        return float(m.train_loss.sum() / m.online_mask.sum())

    base, with_aux = run(0.0), run(0.1)
    assert np.isfinite(base) and np.isfinite(with_aux)
    # the reported loss includes the aux term only when weighted in
    assert abs(with_aux - base) > 1e-6
