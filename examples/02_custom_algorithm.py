"""Writing your own federated algorithm: FedNova in ~30 lines.

The engine treats an algorithm as a set of pure hooks on
``FedAlgorithm`` (algorithms/base.py) — aux-state init, in-loop gradient
transforms, payload construction, the server step. Every built-in
(SCAFFOLD, FedGATE, DRFA, ...) is built from these same hooks, so a new
algorithm needs only the hooks it changes; the engine supplies the jitted
round program, client sampling, sharding, and wire formats.

FedNova (Wang et al. 2020, "Tackling the Objectivity Inconsistency
Problem") normalizes each client's model delta by its own effective
number of local steps before averaging, then rescales the aggregated
update by the mean step count — removing the bias that heterogeneous
local-step counts (epoch-sync mode with skewed shard sizes) introduce
into plain FedAvg. Here that is TWO small hook overrides.

Run:   python examples/02_custom_algorithm.py
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from fedtorch_tpu.algorithms.base import FedAlgorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FederatedConfig, ModelConfig,
    OptimConfig, TrainConfig,
)
from fedtorch_tpu.core.state import tree_scale
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer


class FedNova(FedAlgorithm):
    """Normalized averaging: payload_i = w_i * delta_i / tau_i, and the
    server applies sum_i(payload_i) scaled by the weighted mean tau."""

    name = "fednova"

    def client_payload(self, *, delta, client_aux, params, server_params,
                       server_aux, lr, local_steps, weight,
                       full_loss=None):
        # local_steps is THIS client's effective step count (its
        # epoch-sync budget under skew, or the static K) — exactly
        # FedNova's tau_i. Ship the normalized, weighted delta plus the
        # weighted tau so the server can recover the mean step count.
        tau = jnp.maximum(local_steps.astype(jnp.float32), 1.0)
        payload = tree_scale(delta, weight / tau)
        return {"delta": payload, "wtau": weight * tau}, client_aux

    def server_update(self, server_params, server_opt, server_aux,
                      payload_sum, *, online_idx, num_online_eff,
                      client_losses=None):
        # rescale by the weighted-mean tau, then reuse the standard
        # dual-mode server step (p -= lr_scale_at_sync * d).
        update = tree_scale(payload_sum["delta"], payload_sum["wtau"])
        return super().server_update(
            server_params, server_opt, server_aux, update,
            online_idx=online_idx, num_online_eff=num_online_eff,
            client_losses=client_losses)


def run(algorithm_cls, steps_skew: bool):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=32,
                        batch_size=8),
        federated=FederatedConfig(
            federated=True, num_clients=8, online_client_rate=1.0,
            algorithm="fedavg",
            # epoch-sync over the synthetic dataset's lognormal shard
            # sizes = heterogeneous local step counts, the regime
            # FedNova corrects
            sync_type="epoch" if steps_skew else "local_step",
            num_epochs_per_comm=1),
        model=ModelConfig(arch="mlp", mlp_num_layers=1,
                          mlp_hidden_size=32),
        optim=OptimConfig(lr=0.05),
        train=TrainConfig(local_step=4),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    trainer = FederatedTrainer(cfg, model, algorithm_cls(cfg), data.train)
    server, clients = trainer.init_state(jax.random.key(0))
    loss = float("nan")
    for _ in range(15):
        server, clients, m = trainer.run_round(server, clients)
        loss = float(m.train_loss.sum() / m.online_mask.sum())
    return loss


if __name__ == "__main__":
    for skew in (False, True):
        regime = "skewed epoch-sync" if skew else "uniform local steps"
        base = run(FedAlgorithm, skew)
        nova = run(FedNova, skew)
        print(f"{regime:22s}: fedavg loss {base:.4f}   "
              f"fednova loss {nova:.4f}")
