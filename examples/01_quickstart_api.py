"""Quickstart: federated training through the Python API.

The CLI (`python -m fedtorch_tpu.cli` / `run_tpu.py`) wraps exactly this
sequence; use the API directly when embedding the framework in your own
experiment harness.

Runs in ~a minute on CPU:   python examples/01_quickstart_api.py
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FederatedConfig, ModelConfig,
    OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer, evaluate

# 1. Configuration: typed, immutable, validated by finalize()
#    (the reference's ~90 argparse flags live in these dataclasses).
cfg = ExperimentConfig(
    data=DataConfig(dataset="synthetic", synthetic_dim=32, batch_size=16),
    federated=FederatedConfig(
        federated=True, num_clients=16, online_client_rate=0.5,
        algorithm="fedavg", sync_type="local_step"),
    model=ModelConfig(arch="mlp", mlp_num_layers=1, mlp_hidden_size=64),
    optim=OptimConfig(lr=0.1, in_momentum=True),
    train=TrainConfig(local_step=5),
).finalize()

# 2. Data: per-client shards stacked into [clients, rows, ...] arrays.
#    Non-IID partitioners (label-sort, Dirichlet, natural federation)
#    are selected by cfg.data / cfg.federated fields.
data = build_federated_data(cfg)

# 3. Model + algorithm + trainer. The trainer compiles ONE XLA program
#    for the whole communication round: client sampling, the local-SGD
#    scan, and the aggregation collective.
model = define_model(cfg, batch_size=cfg.data.batch_size)
trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data.train)

# 4. Train. run_round is one jitted call; fit() loops it.
server, clients = trainer.init_state(jax.random.key(0))
for r in range(10):
    server, clients, metrics = trainer.run_round(server, clients)
    online = metrics.online_mask.sum()
    loss = (metrics.train_loss.sum() / online).item()
    print(f"round {r}: mean online train loss {loss:.4f}")

# 5. Evaluate the aggregated server model on the server-side test set.
ev = evaluate(model, server.params, data.test_x, data.test_y)
print(f"final: test loss {float(ev.loss):.4f}  "
      f"top-1 {100 * float(ev.top1):.1f}%")
