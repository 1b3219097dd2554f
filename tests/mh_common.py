"""Shared bring-up for the multi-host worker scripts and tests.

Single source of the multihost contract (env ordering before the first
jax import, shared-seed config/data build) so the
2-process smoke (multihost_worker.py) and the 4-process
interrupt-resume scenario (multihost_resume_worker.py) cannot drift.
"""
from __future__ import annotations

import os
import socket


def free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_workers(script: str, extra_args, n_procs: int, *,
                timeout: int = 420, expect_rc: int = 0):
    """Launch ``n_procs`` coordinated worker processes of ``script``
    (argv: port, pid, *extra_args) and return their merged outputs.

    Single source of the fan-out plumbing: fresh port, repo-root
    PYTHONPATH, communicate-with-timeout + kill-all,
    per-pid returncode assertion (``expect_rc``; the watchdog drill
    expects the restartable code 75 instead of 0). Used by
    test_multihost.py, test_multihost_resume.py and
    test_watchdog_drill.py."""
    import subprocess
    import sys

    import pytest

    port = free_port()
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root, env.get("PYTHONPATH", "")])
    procs = [
        subprocess.Popen(
            [sys.executable, script, str(port), str(pid)]
            + [str(a) for a in extra_args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        for pid in range(n_procs)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"{os.path.basename(script)}: worker timed out")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == expect_rc, (
            f"worker {pid} exited {p.returncode} "
            f"(expected {expect_rc}):\n{out}")
    return outs


def configure_env(local_devices: int) -> None:
    """MUST run before the first ``import jax`` in the process."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               f"{local_devices}")


def bringup(port: str, pid: int, *, num_processes: int,
            local_devices: int, online_client_rate: float):
    """Distributed init + the shared seeded experiment; returns
    (jax, cfg, trainer). Every process derives identical
    data/partitions from the shared seed — the determinism contract
    that replaces the reference's rank-0 broadcast (partition.py:25-33;
    docs/multihost.md 'Determinism across hosts')."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer, init_multihost

    mesh_cfg = MeshConfig(coordinator_address=f"localhost:{port}",
                          num_processes=num_processes, process_id=pid)
    init_multihost(mesh_cfg)
    assert jax.process_count() == num_processes, jax.process_count()
    assert len(jax.local_devices()) == local_devices

    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=12,
                        batch_size=8),
        federated=FederatedConfig(federated=True, num_clients=10,
                                  online_client_rate=online_client_rate,
                                  algorithm="fedavg",
                                  sync_type="local_step"),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.1, weight_decay=0.0),
        train=TrainConfig(local_step=2),
        mesh=mesh_cfg,
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg),
                               data.train)
    assert trainer.mesh.devices.size == num_processes * local_devices
    return jax, cfg, trainer


def round_fingerprint(jax, trainer, server, clients, metrics) -> str:
    """Full-precision per-round fingerprint (loss sum, mean epoch,
    squared param norm) — repr so comparisons are bitwise."""
    loss = float(metrics.train_loss.sum())
    epoch = trainer.mean_client_epoch(clients)
    pnorm = float(sum(jax.numpy.sum(x * x)
                      for x in jax.tree.leaves(server.params)))
    return f"loss={loss!r} epoch={epoch!r} pnorm={pnorm!r}"
