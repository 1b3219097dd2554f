"""The sequential execution (``--client_fusion sequential``): the
cohort one client after another into a running fold. One FedAvg round
against the same round under ``vmap``; what the state and the compiled
round hold; ``validate_cell``'s refusals by name; the launcher with a
specification file through rounds, evaluation, save and resume."""
import json
import os
import re

import jax
import numpy as np
import pytest

from benchmark.datagen import tokens as token_files
from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    CheckpointConfig, DataConfig, ExperimentConfig, FaultConfig, FederatedConfig, MeshConfig,
    ModelConfig, OptimConfig, TelemetryConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer

SPEC = {
    "vocab_size": 64, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 2,
    "layer_types": ["linear_attention", "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 12,
    "linear_conv_kernel_dim": 4, "rms_norm_eps": 1e-6,
}
CLIENTS = 6


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    root = tmp_path_factory.mktemp("tokens")
    spec = root / "spec.json"
    spec.write_text(json.dumps(SPEC))
    data = token_files.write(str(root / "data"), 7, clients=CLIENTS,
                             rows_per_client=5, seq_len=24,
                             vocab_size=SPEC["vocab_size"], test_rows=3)
    return {"spec": str(spec), "data": data}


def lm_cfg(files, execution, *, algorithm="fedavg", spec=None,
           optim=None, fed_kw=None, fault=None, telemetry=None,
           run_dir=None, num_comms=2, resume=None):
    return ExperimentConfig(
        data=DataConfig(dataset="tokens", data_dir=files["data"],
                        batch_size=1),
        federated=FederatedConfig(
            federated=True, num_clients=CLIENTS, online_client_rate=0.5,
            algorithm=algorithm, sync_type="local_step",
            num_comms=num_comms, **(fed_kw or {})),
        model=ModelConfig(arch="hybrid_lm",
                          spec_file=spec or files["spec"]),
        optim=optim or OptimConfig(lr=1e-3, weight_decay=0.0),
        train=TrainConfig(local_step=2, eval_freq=1, manual_seed=11),
        checkpoint=CheckpointConfig(run_dir=run_dir, resume=resume),
        mesh=MeshConfig(num_devices=1, client_fusion=execution,
                        remat=True),
        fault=fault or FaultConfig(),
        telemetry=telemetry or TelemetryConfig(),
    ).finalize()


def trainer_of(cfg):
    data = build_federated_data(cfg).train
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data)


def gauges_of(trainer, metrics) -> dict:
    """The model's own gauges of a round's metrics, by row key."""
    return dict(zip(trainer.gauge_names, metrics.model_gauges or ()))


def with_field_names(text: str, trainer) -> str:
    """A lowered round's text with the model's gauges named as
    ``RoundMetrics``' fields named them until PR 44 made them one tuple
    under the model's own names (``jax.result_info``: a result's name,
    no operation), so that the digests taken before it stand as they
    were and still pin every operation."""
    return re.sub(
        r"(result\[2\])\.model_gauges\[(\d+)\]",
        lambda m: f"{m[1]}.{trainer.gauge_names[int(m[2])]}", text)


@pytest.mark.parametrize("algorithm", ["fedavg", "fedprox"])
def test_sequential_round_equals_the_vmapped_round(files, algorithm):
    """Same cohort, rows, keys and weights: the server's parameters
    agree to float32 rounding of the sum's order, round after round."""
    out = {}
    for execution in ("vmap", "sequential"):
        t = trainer_of(lm_cfg(files, execution, algorithm=algorithm))
        server, clients = t.init_state(jax.random.key(3))
        losses = []
        for _ in range(2):
            server, clients, m = t.run_round(server, clients)
            losses.append(np.asarray(m.train_loss))
        out[execution] = (jax.device_get(server.params), losses,
                          np.asarray(m.online_mask),
                          np.asarray(clients.epoch), t)
    (pv, lv, mv, ev, _), (ps, ls, ms, es, ts) = out["vmap"], \
        out["sequential"]
    np.testing.assert_array_equal(mv, ms)
    np.testing.assert_array_equal(ev, es)
    np.testing.assert_allclose(lv, ls, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(pv), jax.tree.leaves(ps)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7)
    gauges = ts.telemetry_gauges()
    assert gauges["tokens_trained"] == 3 * 2 * 1 * 24
    # remat on and no memory limit known here: every product is kept
    assert gauges["lm_kept_product_share"] == 1.0
    assert gauges["lm_kept_residual_bytes"] > 0


def test_state_holds_no_parameter_sized_leaf_per_client(files):
    t = trainer_of(lm_cfg(files, "sequential"))
    server, clients = t.init_state(jax.random.key(0))
    assert clients.params == () and clients.aux == ()
    shapes = [x.shape for x in jax.tree.leaves(clients)]
    assert shapes == [(CLIENTS,), (CLIENTS,)]
    # no buffer whose momentum is off, on the server either
    assert jax.tree.leaves(server.opt) == []
    vm_server, vm_clients = trainer_of(lm_cfg(files, "vmap")).init_state(
        jax.random.key(0))
    n = len(jax.tree.leaves(server.params))
    assert len(jax.tree.leaves(vm_clients)) == 3 * n + 2
    assert len(jax.tree.leaves(vm_server.opt)) == 2 * n


DEEP = dict(
    SPEC, hidden_size=256, intermediate_size=704, num_hidden_layers=6,
    layer_types=["linear_attention", "linear_attention",
                 "full_attention"] * 2,
    num_attention_heads=8, num_key_value_heads=8,
    linear_num_key_heads=8, linear_num_value_heads=8,
    linear_key_head_dim=24, linear_value_head_dim=48)


def test_compiled_round_holds_under_four_trees(tmp_path):
    """Arguments and temporaries of the compiled sequential round,
    counted by the compiler (a count, valid on the CPU), at a size
    where activations are negligible beside the parameters (5.4 M of
    them in 80 leaves, rows of 8 tokens): the server's parameters, the
    running client's, the running sum, and what of a step's gradient
    this backend keeps (it does not fuse the update into the
    weight-gradient product as the TPU's does: 3.80 trees here, where
    the vmapped round holds 3 (C + 1) + 2k)."""
    path = tmp_path / "deep.json"
    path.write_text(json.dumps(DEEP))
    data = token_files.write(str(tmp_path / "data"), 1, clients=CLIENTS,
                             rows_per_client=3, seq_len=8,
                             vocab_size=DEEP["vocab_size"], test_rows=1)
    files = {"data": data}

    def held_trees(execution):
        t = trainer_of(lm_cfg(files, execution, spec=str(path)))
        server, clients = jax.eval_shape(t.init_state, jax.random.key(0))
        tree = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(server.params))
        assert tree > 2e7
        mem = jax.jit(t.round_fn, donate_argnums=(0, 1)).lower(
            server, clients, t.data, None).compile().memory_analysis()
        return (mem.argument_size_in_bytes
                + mem.temp_size_in_bytes) / tree

    assert held_trees("sequential") < 4.0
    assert held_trees("vmap") > 3 * (CLIENTS + 1)


REFUSALS = [
    (dict(algorithm="scaffold"), "no fold yet"),
    (dict(algorithm="fedgate"), "no fold yet"),
    (dict(fault=FaultConfig(robust_agg="median")), "robust_agg='median'"),
    (dict(fault=FaultConfig(guard_updates=True)), "update guards"),
    (dict(fault=FaultConfig(client_drop_rate=0.2)), "chaos"),
    (dict(fault=FaultConfig(dp_noise_multiplier=1.0, dp_clip_norm=1.0)),
     "DP stage"),
    (dict(telemetry=TelemetryConfig(cohort_stats=True)), "cohort_stats"),
    (dict(fed_kw=dict(quantized=True)), "wire format"),
    (dict(optim=OptimConfig(lr=1e-3, in_momentum=True)),
     "local optimizer with buffers"),
    (dict(optim=OptimConfig(lr=1e-3, optimizer="adam")),
     "local optimizer with buffers"),
]


@pytest.mark.parametrize("kw,match", REFUSALS,
                         ids=[m for _, m in REFUSALS])
def test_validate_cell_refuses_by_name(files, kw, match):
    with pytest.raises(ValueError) as err:
        trainer_of(lm_cfg(files, "sequential", **kw))
    text = str(err.value)
    assert "round-program cell (resident x round x sequential)" in text
    assert "client_fusion='sequential'" in text and match in text


def round_rows(run_dir):
    from fedtorch_tpu.telemetry.schema import load_jsonl, stitch_rows
    _, records, _ = load_jsonl(os.path.join(run_dir, "metrics.jsonl"))
    return [r for r in stitch_rows(records) if "round_s" in r]


def test_launcher_runs_saves_and_resumes(files, tmp_path):
    from fedtorch_tpu.cli import run_experiment
    run_dir = str(tmp_path / "run")
    first = run_experiment(lm_cfg(files, "sequential", run_dir=run_dir,
                                  num_comms=2))
    assert 0.0 <= first["test_top1"] <= 1.0
    with open(os.path.join(run_dir, "checkpoint.json")) as f:
        assert json.load(f)["round"] == 2
    rows = round_rows(run_dir)
    assert [r["round"] for r in rows] == [0, 1]
    assert all(r["tokens_trained"] == 3 * 2 * 24 for r in rows)
    assert all(np.isfinite(r["loss"]) for r in rows)
    # the save holds the server's model and the clients' counters
    size = os.path.getsize(os.path.join(run_dir, "checkpoint.ckpt"))
    t = trainer_of(lm_cfg(files, "sequential"))
    server, _ = jax.eval_shape(t.init_state, jax.random.key(0))
    tree = sum(x.size * 4 for x in jax.tree.leaves(server.params))
    assert tree < size < 1.2 * tree + 4096
    again = run_experiment(lm_cfg(files, "sequential", run_dir=run_dir,
                                  num_comms=3, resume=run_dir))
    assert [r["round"] for r in round_rows(run_dir)] == [0, 1, 2]
    assert 0.0 <= again["test_top1"] <= 1.0
