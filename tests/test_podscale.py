"""Pod-scale round programs (ISSUE 20): the client-axis sharded cells.

The engine-wide bars, on the 8-device XLA-forced CPU mesh every tier-1
run carries:

* **S-shard parity** — every legal (source x dispatch x vmap) cell at
  ``mesh.client_shards`` S in {2, 4} is BITWISE-identical per round to
  its armed 1-shard twin (the S=1 2-D mesh running the same grouped
  hierarchical aggregation seam), and traces exactly once;
* **degraded-pod resume** — a checkpoint taken at S=4 restored onto
  S=2 continues the S=1 trajectory bitwise (the hierarchical sum's
  association is a function of k alone, never of S);
* **named refusals** — each illegal sharded composition (the
  sequential execution, non-dividing cohort, robust rules, cohort
  stats, uncertified algorithms, shard gather mode, non-dividing commit
  buffer) raises ONE ValueError naming the cell from validate_cell,
  including the sequential execution's multi-device refusal with its
  exact message;
* **torn-shard recovery** — under per-host sharded packing a torn
  ``MmapClientStore`` shard escalates through the
  'stream.gather' -> 'stream.producer' chain NAMING the owning
  host/shard, and after the file heals the run recovers bitwise.
"""
import re

import jax
import numpy as np
import pytest

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
    MeshConfig, ModelConfig, OptimConfig, TelemetryConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.parallel.mesh import (
    local_cohort_rows, mesh_client_shards, replicate, shard_clients,
)
from fedtorch_tpu.parallel.podscale import (
    cohort_group_count, cohort_hierarchical_sum,
)
from fedtorch_tpu.parallel.round_program import (
    DISPATCHES, SOURCES, illegal_reason,
)
from fedtorch_tpu.robustness import HostSeamError
from fedtorch_tpu.utils.tracing import RecompilationSentinel

SHARD_COUNTS = (1, 2, 4)
VMAP_CELLS = [(s, d) for s in SOURCES for d in DISPATCHES]


def make_cfg(source, dispatch, shards, *, num_clients=8, rate=0.5,
             store="ram", store_dir="", fault_kw=None, telemetry_kw=None,
             algorithm="fedavg", gather_mode=None, buffer_size=4,
             fusion="vmap"):
    plane = "stream" if source == "feed" else "device"
    sync_mode = "async" if dispatch == "commit" else "sync"
    return ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=16,
                        batch_size=8, synthetic_alpha=0.5,
                        synthetic_beta=0.5, data_plane=plane,
                        store=store, store_dir=store_dir),
        federated=FederatedConfig(
            federated=True, num_clients=num_clients,
            online_client_rate=rate, algorithm=algorithm,
            sync_type="local_step", sync_mode=sync_mode,
            async_buffer_size=buffer_size, async_concurrency=4),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.3, weight_decay=0.0),
        train=TrainConfig(local_step=2),
        mesh=MeshConfig(client_shards=shards, client_fusion=fusion),
        fault=FaultConfig(**(fault_kw or {})),
        telemetry=TelemetryConfig(**(telemetry_kw or {})),
    ).finalize()


def build_trainer(cfg, data=None):
    data = data if data is not None else build_federated_data(cfg).train
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    if cfg.federated.sync_mode == "async":
        from fedtorch_tpu.async_plane import AsyncFederatedTrainer
        return AsyncFederatedTrainer(cfg, model, make_algorithm(cfg),
                                     data)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data)


def run_cell(trainer, dispatch, rounds=2, seed=3):
    server, clients = trainer.init_state(jax.random.key(seed))
    metrics = []
    if dispatch == "scan":
        server, clients, ms = trainer.run_rounds(server, clients,
                                                 rounds)
        metrics.append(jax.tree.map(np.asarray, ms))
    else:
        for _ in range(rounds):
            server, clients, m = trainer.run_round(server, clients)
            metrics.append(jax.tree.map(np.asarray, m))
    trainer.invalidate_stream()
    return (jax.tree.map(np.asarray, (server.params, server.aux)),
            jax.tree.map(np.asarray, clients), metrics)


def cell_trace_name(trainer, source, dispatch, rounds=2):
    if dispatch == "round":
        return trainer.trace_name if source == "resident" \
            else trainer.stream_trace_name
    if dispatch == "commit":
        return trainer.commit_trace_name if source == "resident" \
            else trainer.commit_stream_trace_name
    suffix = "" if source == "resident" else "_stream"
    return (f"federated.rounds{suffix}"
            f"[{trainer.algorithm.name}]x{rounds}")


def assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# armed-S=1 twin trajectories, computed once per (source, dispatch)
_TWINS = {}


def twin(source, dispatch):
    key = (source, dispatch)
    if key not in _TWINS:
        t = build_trainer(make_cfg(source, dispatch, 1))
        _TWINS[key] = run_cell(t, dispatch)
    return _TWINS[key]


# -- the parity matrix -------------------------------------------------------
@pytest.mark.parametrize("source,dispatch", VMAP_CELLS)
@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_sharded_cell_bitwise_vs_one_shard_twin(source, dispatch,
                                                shards):
    """Every legal sharded vmap cell: bitwise-identical per round to
    its armed 1-shard twin, and its program traces exactly once."""
    if len(jax.devices()) % shards:
        pytest.skip(f"device count does not divide {shards} ways")
    trainer = build_trainer(make_cfg(source, dispatch, shards))
    assert trainer.client_shards == shards
    assert trainer.podscale_armed
    with RecompilationSentinel() as sentinel:
        got = run_cell(trainer, dispatch)
        jax.block_until_ready(jax.tree.leaves(got[0]))
    sentinel.assert_traces(cell_trace_name(trainer, source, dispatch),
                           expected=1)
    if shards == 1:
        _TWINS[(source, dispatch)] = got  # it IS the twin
        return
    assert_trees_equal(got, twin(source, dispatch))


def test_degraded_pod_resume_halves_shards_bitwise():
    """An S=4 checkpoint restored onto S=2 shards continues the armed
    S=1 trajectory bitwise: the hierarchical sum's association depends
    on k alone, so halving the pod replays identical scalar adds."""
    seed, pre, post = 7, 2, 2
    # the uninterrupted reference: armed S=1, pre+post rounds
    t1 = build_trainer(make_cfg("resident", "round", 1))
    ref = run_cell(t1, "round", rounds=pre + post, seed=seed)

    t4 = build_trainer(make_cfg("resident", "round", 4))
    server, clients = t4.init_state(jax.random.key(seed))
    for _ in range(pre):
        server, clients, _ = t4.run_round(server, clients)
    # "checkpoint": pure host bytes, exactly what orbax-style save
    # would serialize — no device placement survives
    ckpt = jax.device_get((server, clients))
    t4.invalidate_stream()

    t2 = build_trainer(make_cfg("resident", "round", 2))
    assert mesh_client_shards(t2.mesh) == 2
    server2 = replicate(ckpt[0], t2.mesh)
    clients2 = shard_clients(ckpt[1], t2.mesh)
    metrics = []
    for _ in range(post):
        server2, clients2, m = t2.run_round(server2, clients2)
        metrics.append(jax.tree.map(np.asarray, m))
    t2.invalidate_stream()
    assert_trees_equal(
        (jax.tree.map(np.asarray, (server2.params, server2.aux)),
         jax.tree.map(np.asarray, clients2), metrics),
        (ref[0], ref[1], ref[2][pre:]))


# -- telemetry gauges (ISSUE 20 satellite: registry-visible) ----------------
def test_podscale_gauges_surface_in_telemetry():
    cfg = make_cfg("feed", "round", 2)
    t = build_trainer(cfg)
    server, clients = t.init_state(jax.random.key(0))
    server, clients, _ = t.run_round(server, clients)
    g = t.telemetry_gauges()
    assert g["client_shards"] == 2.0
    assert g["cohort_allreduce_bytes"] > 0.0
    # single-process runs own every shard, so the producer packs the
    # full cohort — the gauge still reports the sharded-pack path
    assert g["stream_shard_rows"] == float(t.k_dispatch)
    assert g["stream_shard_pack_s"] >= 0.0
    t.invalidate_stream()


def test_hierarchical_sum_is_shard_invariant_standalone():
    """The seam in isolation: S in {1, 2, 4} over the same [k, P]
    payloads produce identical bytes, and the group count is a
    function of k alone."""
    from fedtorch_tpu.parallel.mesh import make_mesh
    k = 8
    assert cohort_group_count(k) == 8
    rng = np.random.RandomState(0)
    payloads = {"w": rng.randn(k, 5).astype(np.float32),
                "n": rng.randint(0, 9, (k,)).astype(np.int32)}
    outs = {}
    for S in SHARD_COUNTS:
        mesh = make_mesh(MeshConfig(client_shards=S))
        arr = jax.device_put(
            jax.tree.map(np.copy, payloads))
        outs[S] = jax.tree.map(np.asarray, jax.jit(
            lambda p: cohort_hierarchical_sum(p, mesh, S))(arr))
    assert_trees_equal(outs[1], outs[2])
    assert_trees_equal(outs[1], outs[4])


# -- named refusals ---------------------------------------------------------
def _reason(cfg, source="resident", dispatch="round",
            execution="vmap", k_online=4, mesh_devices=8):
    alg = make_algorithm(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    return illegal_reason(source, dispatch, execution, cfg=cfg,
                          algorithm=alg, model=model,
                          mesh_devices=mesh_devices, k_online=k_online)


class TestShardedRefusals:
    def test_sequential_execution_refused_under_sharding(self):
        reason = _reason(make_cfg("resident", "round", 2),
                         execution="sequential")
        assert "the fold is one device's (mesh has 8 devices; " \
            "client_shards must be 0)" in reason

    def test_non_dividing_cohort_refused(self):
        with pytest.raises(ValueError, match="does not divide the "
                                             "dispatch cohort width"):
            build_trainer(make_cfg("resident", "round", 4,
                                   num_clients=12))  # k=6, S=4

    def test_robust_rules_refused(self):
        with pytest.raises(ValueError, match="robust_agg"):
            build_trainer(make_cfg(
                "resident", "round", 2,
                fault_kw={"robust_agg": "median"}))

    def test_cohort_stats_refused(self):
        with pytest.raises(ValueError, match="cohort_stats"):
            build_trainer(make_cfg(
                "resident", "round", 2,
                telemetry_kw={"cohort_stats": True}))

    def test_uncertified_algorithm_refused(self):
        with pytest.raises(ValueError, match="not certified"):
            build_trainer(make_cfg("resident", "round", 2,
                                   algorithm="qffl"))

    def test_shard_gather_mode_refused(self):
        cfg = make_cfg("resident", "round", 2)
        data = build_federated_data(cfg).train
        model = define_model(cfg, batch_size=cfg.data.batch_size)
        with pytest.raises(ValueError,
                           match="not bitwise-stable across shard"):
            FederatedTrainer(cfg, model, make_algorithm(cfg), data,
                             gather_mode="shard")

    def test_auto_gather_never_resolves_shard_when_armed(self):
        # K*B >= n_max would pick 'shard' on a legacy mesh; armed
        # meshes must resolve 'batch' so every shard count traces the
        # same in-program gather plan
        cfg = make_cfg("resident", "round", 2, num_clients=8)
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=cfg.data.batch_size)
        t = FederatedTrainer(cfg, model, make_algorithm(cfg),
                             data.train)
        assert t.gather_mode == "batch"

    def test_non_dividing_commit_buffer_refused(self):
        with pytest.raises(ValueError, match="async commit buffer"):
            build_trainer(make_cfg("resident", "commit", 2,
                                   buffer_size=3))

    def test_non_dividing_device_mesh_refused(self):
        from fedtorch_tpu.parallel.mesh import make_mesh
        with pytest.raises(ValueError, match="does not divide the"):
            make_mesh(MeshConfig(client_shards=3))


# -- the sequential cell's multi-device refusal ------------------------------
def test_sequential_multi_device_refusal_exact_message():
    """The execution axis's one multi-device rule lives in
    validate_cell: the EXACT message, raised at trainer construction
    on a multi-device mesh."""
    n = len(jax.devices())
    expected = (
        "round-program cell (resident x round x sequential) is "
        "unsupported here: mesh.client_fusion='sequential' runs the "
        "cohort one client after another into a running weighted sum "
        "and keeps no per-client copy of the parameters: the fold is "
        f"one device's (mesh has {n} devices; client_shards must be 0)")
    with pytest.raises(ValueError, match=re.escape(expected)):
        build_trainer(make_cfg("resident", "round", 0,
                               fusion="sequential"))  # all 8 devices


# -- torn-shard recovery under per-host sharded packing ---------------------
def test_torn_shard_names_owner_and_recovers_bitwise(tmp_path):
    """Under pod-scale per-host packing a torn MmapClientStore shard
    must escalate 'stream.gather' -> 'stream.producer' NAMING the
    owning host and store shard; healing the file and resyncing the
    producer recovers the trajectory bitwise."""
    from fedtorch_tpu.data.streaming import save_client_store
    cfg = make_cfg("feed", "round", 2, store="mmap",
                   store_dir=str(tmp_path))
    data = build_federated_data(cfg)
    save_client_store(str(tmp_path), data.train, clients_per_shard=3)

    # the untouched twin (same sharded config, pristine store)
    twin_t = build_trainer(cfg, data.train)
    ref = run_cell(twin_t, "round", rounds=2, seed=5)

    t = build_trainer(cfg, data.train)
    assert local_cohort_rows(t.mesh, t.k_dispatch,
                             t.client_shards) == (0, t.k_dispatch)
    server, clients = t.init_state(jax.random.key(5))
    torn = {p: p.read_bytes() for p in tmp_path.glob("x.*.bin")}
    for p in torn:
        p.write_bytes(torn[p][:16])  # tear every x shard
    try:
        with pytest.raises(HostSeamError) as ei:
            for _ in range(3):
                server, clients, _ = t.run_round(server, clients)
        assert ei.value.seam == "stream.producer"
        chain, exc = [], ei.value
        while exc is not None:
            chain.append(str(exc))
            exc = exc.__cause__
        blob = " | ".join(chain)
        assert "client-store shard" in blob
        assert "owning host: process 0" in blob
        assert "torn or truncated" in blob

        for p, b in torn.items():  # heal and resync
            p.write_bytes(b)
        t.invalidate_stream()
        metrics = []
        for _ in range(2):
            server, clients, m = t.run_round(server, clients)
            metrics.append(jax.tree.map(np.asarray, m))
        assert_trees_equal(
            (jax.tree.map(np.asarray, (server.params, server.aux)),
             jax.tree.map(np.asarray, clients), metrics),
            ref)
    finally:
        t.invalidate_stream()
