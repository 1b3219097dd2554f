"""Full-state checkpoint / resume — including what the reference loses.

The reference checkpoints only the server's aggregated model
(logs/checkpoint.py:68-82): client control variates, error-feedback
memory, personal models, and dual weights all restart from zero on
resume. Here the checkpoint is the ENTIRE round state — ServerState +
every client's algorithm aux + the threaded PRNG key — so a resumed run
continues bit-exactly, demonstrated below with SCAFFOLD (whose control
variates are exactly the state the reference would lose).

Also shows AsyncCheckpointer: the same writes from a background thread
(atomic tmp+fsync+rename), so training dispatch never blocks on disk.

Run (no TPU needed):
    JAX_PLATFORMS=cpu python examples/05_checkpoint_resume.py
"""
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FederatedConfig, ModelConfig,
    OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.utils import (
    AsyncCheckpointer, maybe_resume, save_checkpoint,
)

cfg = ExperimentConfig(
    data=DataConfig(dataset="synthetic", synthetic_dim=20, batch_size=16),
    federated=FederatedConfig(federated=True, num_clients=8,
                              online_client_rate=0.5,
                              algorithm="scaffold",
                              sync_type="local_step"),
    model=ModelConfig(arch="logistic_regression"),
    optim=OptimConfig(lr=0.1, weight_decay=0.0),
    train=TrainConfig(local_step=3),
).finalize()
data = build_federated_data(cfg)
model = define_model(cfg, batch_size=16)
trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data.train)

server, clients = trainer.init_state(jax.random.key(0))
for _ in range(3):
    server, clients, _ = trainer.run_round(server, clients)
print(f"trained to round {int(server.round)} (SCAFFOLD, 8 clients)")

with tempfile.TemporaryDirectory() as tmp:
    # --- synchronous save -------------------------------------------
    save_checkpoint(tmp, server, clients, cfg, best_prec1=0.0,
                    is_best=False)
    print("saved: server params + every client's control variates + rng")

    # --- restore into FRESH state -----------------------------------
    s2, c2 = trainer.init_state(jax.random.key(0))
    s2, c2, _, resumed = maybe_resume(tmp, s2, c2, cfg, None)
    assert resumed and int(s2.round) == 3
    ctrl_a = jax.tree.leaves(clients.aux["control"])
    ctrl_b = jax.tree.leaves(c2.aux["control"])
    err = max(float(abs(np.asarray(a) - np.asarray(b)).max())
              for a, b in zip(ctrl_a, ctrl_b))
    print(f"control variates restored, max err = {err:.1e}")

    # --- the resumed run continues EXACTLY --------------------------
    # (run_round DONATES its inputs; keep the returned states)
    s_cont, c_cont, m1 = trainer.run_round(server, clients)
    s_res, c_res, m2 = trainer.run_round(s2, c2)
    perr = max(float(abs(np.asarray(a) - np.asarray(b)).max())
               for a, b in zip(jax.tree.leaves(s_cont.params),
                               jax.tree.leaves(s_res.params)))
    print(f"round 4 after resume: server-param divergence = {perr:.1e}")
    assert perr == 0.0

with tempfile.TemporaryDirectory() as tmp:
    # --- async: identical bytes, off the critical path --------------
    ck = AsyncCheckpointer()
    ck.save(tmp, s_res, c_res, cfg, best_prec1=0.0, is_best=False)
    ck.close()  # flush before reading back
    s3, c3 = trainer.init_state(jax.random.key(0))
    _, _, _, resumed = maybe_resume(tmp, s3, c3, cfg, None)
    assert resumed
    print("async checkpoint written in the background and resumed")
print("ok: full round state (incl. SCAFFOLD control variates) survives "
      "resume bit-exactly")
