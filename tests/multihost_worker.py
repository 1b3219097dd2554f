"""Worker process for the multi-host smoke test (test_multihost.py).

Each of the two processes owns 4 virtual CPU devices; together they form
an 8-device global mesh over which one federated round executes — the
DCN analog of the reference's ``dist.init_process_group('mpi')`` bring-up
(main.py:17). Bring-up shared with the 4-process interrupt-resume
scenario via mh_common.py. Run as:

    python tests/multihost_worker.py <port> <process_id> [ckpt_dir]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from mh_common import bringup, configure_env  # noqa: E402

port, pid = sys.argv[1], int(sys.argv[2])
configure_env(local_devices=4)  # before the first jax import

jax, cfg, trainer = bringup(port, pid, num_processes=2,
                            local_devices=4, online_client_rate=1.0)
assert len(jax.devices()) == 8, jax.devices()
assert trainer.padded_clients == 16  # 10 clients padded over 8 devices

server, clients = trainer.init_state(jax.random.key(0))
leaf = jax.tree.leaves(clients.params)[0]
assert len(leaf.sharding.device_set) == 8, leaf.sharding

for _ in range(2):
    server, clients, metrics = trainer.run_round(server, clients)
jax.block_until_ready(server.params)

# checkpoint across hosts: the snapshot is a COLLECTIVE (client state
# is sharded across the two processes and must be allgathered); only
# process 0 writes. Both processes MUST make the call.
ckpt_dir = sys.argv[3] if len(sys.argv) > 3 else None
if ckpt_dir:
    from jax.experimental import multihost_utils
    from fedtorch_tpu.utils import maybe_resume, save_checkpoint
    save_checkpoint(ckpt_dir, server, clients, cfg, best_prec1=0.25,
                    is_best=False)
    if pid == 0:
        assert os.path.exists(os.path.join(ckpt_dir, "checkpoint.ckpt"))
    # barrier: process 1 must not read before process 0's write lands
    multihost_utils.sync_global_devices("checkpoint-written")
    # resume restores the sharded state on BOTH processes
    s2, c2 = trainer.init_state(jax.random.key(1))
    s2, c2, best, resumed = maybe_resume(ckpt_dir, s2, c2, cfg, None)
    assert resumed and best == 0.25 and int(s2.round) == 2
    server2, clients2, m2 = trainer.run_round(s2, c2)
    jax.block_until_ready(server2.params)
    print(f"MULTIHOST_CKPT_OK pid={pid}", flush=True)

# replicated scalars are fetchable on every host
loss = float(metrics.train_loss.sum()) / 10.0
epoch = trainer.mean_client_epoch(clients)
assert loss == loss and epoch > 0, (loss, epoch)
# so is the round's one fetch (one program, one replicated array), and
# the stop flag one process raises reaches both as the max
trainer.attach_stop_signal(lambda: pid == 1)
sc = trainer.round_host_scalars(clients, metrics)
assert sc["stop"] == 1.0, sc
assert sc["mean_epoch"] == epoch and sc["loss_sum"] / 10.0 == loss, sc
assert sc["n_online"] == 10.0 and sc["lr"] > 0.0, sc
print(f"MULTIHOST_OK pid={pid} loss={loss:.6f} epoch={epoch:.3f}",
      flush=True)
jax.distributed.shutdown()
