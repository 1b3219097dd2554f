"""The round's log scalars as ONE compiled program (ISSUE 34).

``FederatedTrainer.round_scalars_dev`` built the round's dict of log
scalars with eager ``jnp`` calls (48 device programs a round on the
chip, most of them inside ``lr_at``) and seventeen copies brought it
over; ``round_host_fetch`` runs one jitted program
(``trainer.scalars_trace_name``) whose one array one copy brings over.
The eager body lives on here, as the oracle: the fetched dict must
carry the same keys and the same values bit for bit: a logged ``lr``
that differs in the last place from ``lr_at``'s eager value is a
defect, not a tolerance. A CPU run gives counts and bits, never a time.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FaultConfig, FederatedConfig, LRConfig,
    MeshConfig, ModelConfig, OptimConfig, TelemetryConfig, TrainConfig,
)
from fedtorch_tpu.core.schedule import lr_at
from fedtorch_tpu.core.state import RoundMetrics
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.utils.tracing import RecompilationSentinel

DP = dict(dp_noise_multiplier=1.0, dp_clip_norm=0.5, dp_delta=1e-5)
MESHES = {"one": MeshConfig(num_devices=1), "eight": MeshConfig(),
          "pod2": MeshConfig(client_shards=2)}


def make_trainer(*, cohort=False, dp=False, mode="perm", mesh="eight",
                 lr_schedule=None, num_epochs=3, optim_lr=0.3,
                 num_clients=8):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=20,
                        batch_size=16, synthetic_alpha=0.5,
                        synthetic_beta=0.5),
        federated=FederatedConfig(
            # a federated run's num_epochs, the schedule's last edge,
            # is num_comms x the online rate
            federated=True, num_clients=num_clients,
            num_comms=2 * num_epochs,
            online_client_rate=0.5, algorithm="fedavg",
            sync_type="local_step", participation_mode=mode),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=optim_lr, weight_decay=0.0),
        lr_schedule=lr_schedule or LRConfig(),
        train=TrainConfig(local_step=2),
        mesh=MESHES[mesh],
        fault=FaultConfig(**DP) if dp else FaultConfig(),
        telemetry=TelemetryConfig(cohort_stats=cohort),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data.train)


def eager_scalars(trainer, clients, metrics, stop=None):
    """``round_scalars_dev`` and its fetch as they were before ISSUE
    34: one eager device program an operation, one copy a scalar. The
    oracle."""
    mean_epoch = jnp.mean(clients.epoch[:trainer.num_clients])
    out = {
        "mean_epoch": mean_epoch,
        "lr": lr_at(trainer.schedule, mean_epoch),
        "n_online": jnp.sum(metrics.online_mask),
        "loss_sum": jnp.sum(metrics.train_loss),
        "acc_sum": jnp.sum(metrics.train_acc),
        "comm_bytes": metrics.comm_bytes,
        "dropped": metrics.dropped_clients,
        "stragglers": metrics.straggler_clients,
        "rejected": metrics.rejected_updates,
        "clipped": metrics.clipped_updates,
        "staleness": metrics.staleness_mean,
        "byzantine": metrics.byzantine_clients,
        "robust_selected": metrics.robust_selected,
        "robust_trimmed": metrics.robust_trimmed,
        "avail_dropped": metrics.avail_dropped,
        "deadline_missed": metrics.deadline_missed,
        "quorum_degraded": metrics.quorum_degraded,
    }
    if metrics.cohort_dispersion is not None:
        out["cohort_dispersion"] = metrics.cohort_dispersion
    if metrics.dp_clipped_frac is not None:
        out["dp_clipped_frac"] = metrics.dp_clipped_frac
        out["dp_noise_sigma"] = metrics.dp_noise_sigma
    if stop is not None:
        out["stop"] = jnp.asarray(np.float32(1.0 if stop else 0.0))
    return {k: float(v) for k, v in jax.device_get(out).items()}


def bits(x: float) -> bytes:
    return np.float64(x).tobytes()


def assert_same_scalars(got: dict, want: dict):
    assert set(got) == set(want)
    for k in want:
        assert type(got[k]) is float, k
        assert bits(got[k]) == bits(want[k]), (k, got[k], want[k])


@pytest.mark.parametrize("mode", ["perm", "sparse"])
@pytest.mark.parametrize("stop", [False, True],
                         ids=["nostop", "stop"])
@pytest.mark.parametrize("dp", [False, True], ids=["nodp", "dp"])
@pytest.mark.parametrize("cohort", [False, True],
                         ids=["nocohort", "cohort"])
def test_compiled_scalars_equal_the_eager_body(cohort, dp, stop, mode):
    trainer = make_trainer(cohort=cohort, dp=dp, mode=mode)
    flag = {"stop": False}
    if stop:
        trainer.attach_stop_signal(lambda: flag["stop"])
    server, clients = trainer.init_state(jax.random.key(7))
    for r in range(3):
        flag["stop"] = r == 1
        server, clients, metrics = trainer.run_round(server, clients)
        got = trainer.round_host_scalars(clients, metrics)
        want = eager_scalars(trainer, clients, metrics,
                             stop=flag["stop"] if stop else None)
        assert_same_scalars(got, want)
        # the optional keys follow the metrics' structure: absent, not 0
        assert ("cohort_dispersion" in got) == cohort
        assert ("dp_clipped_frac" in got) == dp
        assert ("dp_noise_sigma" in got) == dp
        assert ("stop" in got) == stop
        if stop:
            assert got["stop"] == float(r == 1)
    assert got["n_online"] == 4.0 and got["mean_epoch"] > 0.0


@pytest.mark.parametrize("num_clients,mesh", [
    (10, "eight"), (13, "eight"), (100, "eight"),
    (12, "pod2"), (100, "pod2")])  # the pod's two shards divide k
def test_padded_client_axis_sums_as_the_eager_slice_did(num_clients,
                                                        mesh):
    """A client axis padded up to the mesh (16 or 104 rows over eight
    devices): the eager slice of the real clients came back replicated
    and was summed in index order; the program does the same, where a
    sum of per-shard sums reads one place off in the last."""
    trainer = make_trainer(num_clients=num_clients, mesh=mesh)
    assert trainer.padded_clients > num_clients
    server, clients = trainer.init_state(jax.random.key(2))
    for _ in range(6):
        server, clients, metrics = trainer.run_round(server, clients)
        assert_same_scalars(trainer.round_host_scalars(clients, metrics),
                            eager_scalars(trainer, clients, metrics))


# -- the schedule inside the program ---------------------------------------
# (LRConfig, optimizer lr, num_epochs, the fields' edges)
SCHEMES = {
    # "10,5": fields (0,10) (10,5) (5,30): the second is empty, the
    # third overlaps the first on [5, 10): first match wins
    "strict_overlap": (
        LRConfig(schedule_scheme="strict", lr_change_epochs="10,5",
                 lr_fields="0.1,0.2/0.3,0.3/0.01,0.001",
                 lr_scale_indicators="0,0,1"), 0.1, 30, (0, 5, 10, 30)),
    "custom_one_cycle": (
        LRConfig(schedule_scheme="custom_one_cycle", onecycle_low=0.15,
                 onecycle_high=3.0, onecycle_extra_low=0.0015,
                 onecycle_num_epoch=10), 0.1, 14, (0, 5, 10, 14)),
    # warm-up to 6 reaches past the first change epoch 4: fields (0,6)
    # (6,4) (4,9) (9,12) overlap
    "custom_multistep_warmup": (
        LRConfig(schedule_scheme="custom_multistep",
                 lr_change_epochs="4,9", decay=10.0, warmup=True,
                 warmup_epochs=6, scaleup=True, scaleup_factor=4.0),
        0.05, 12, (0, 4, 6, 9, 12)),
    "custom_convex_decay": (
        LRConfig(schedule_scheme="custom_convex_decay", gamma=2.0,
                 mu=0.5, alpha=3.0), 0.1, 8, (0, 8)),
}


@functools.lru_cache(maxsize=None)
def scheme_fixture(scheme):
    lr_cfg, lr, num_epochs, edges = SCHEMES[scheme]
    trainer = make_trainer(lr_schedule=lr_cfg, num_epochs=num_epochs,
                           optim_lr=lr)
    server, clients = trainer.init_state(jax.random.key(0))
    server, clients, metrics = trainer.run_round(server, clients)
    return trainer, clients, metrics, edges


@pytest.fixture(scope="module", autouse=True)
def _let_go_of_the_cached_trainers():
    """The cache above would keep four trainers' device stores alive
    for the rest of the worker's session, where a later file counts
    what is live on the device (test_streaming's residency case failed
    whenever the two files shared a worker)."""
    yield
    scheme_fixture.cache_clear()


@pytest.mark.parametrize("where", ["before", "on", "past"])
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_program_lr_is_lr_at(scheme, where):
    """The compiled ``lr`` against eager ``lr_at`` before, on and past
    every edge: first match wins, the last field saturates."""
    trainer, clients, metrics, edges = scheme_fixture(scheme)
    shift = {"before": -0.25, "on": 0.0, "past": 0.25}[where]
    epochs = [max(e + shift, 0.0) for e in edges]
    if where == "past":
        epochs.append(edges[-1] + 7.5)  # far past the last edge
    for e in epochs:
        # every client at epoch e: the mean over 8 equal values is e
        at = clients._replace(epoch=jax.device_put(
            np.full(clients.epoch.shape, e, np.float32),
            clients.epoch.sharding))
        got = trainer.round_host_scalars(at, metrics)
        assert bits(got["mean_epoch"]) == bits(float(np.float32(e)))
        want = float(lr_at(trainer.schedule, np.float32(e)))
        assert bits(got["lr"]) == bits(want), (scheme, e, got["lr"], want)
        assert_same_scalars(got, eager_scalars(trainer, at, metrics))


def test_first_match_wins_and_last_field_saturates():
    """What the edges above mean, in numbers: the oracle is not wrong
    in the same way."""
    trainer, clients, metrics, _ = scheme_fixture("strict_overlap")

    def lr(e):
        at = clients._replace(epoch=jax.device_put(
            np.full(clients.epoch.shape, e, np.float32),
            clients.epoch.sharding))
        return trainer.round_host_scalars(at, metrics)["lr"]

    # [5, 10) lies in the first field (linear 0.1 -> 0.2 over 0..10)
    # and in the third: the first wins
    assert lr(7.5) == pytest.approx(0.175, rel=1e-6)
    # past the last edge the last field's value at that epoch (poly)
    assert lr(31.0) == float(lr_at(trainer.schedule, 31.0))
    assert lr(12.0) == pytest.approx(0.01 * (1 - 7 / 25) ** 2, rel=1e-5)


# -- one trace, one program -------------------------------------------------
@pytest.mark.parametrize("stop", ["nostop", "flipping"])
@pytest.mark.parametrize("mesh", sorted(MESHES))
def test_scalar_program_traces_once(mesh, stop):
    """Three rounds then three more fetches: the scalar program is
    traced once, whatever the stop flag does between rounds (it is a
    host value on one process and never enters the program)."""
    trainer = make_trainer(mesh=mesh)
    assert int(trainer.mesh.devices.size) == (1 if mesh == "one" else 8)
    flag = {"stop": False}
    if stop == "flipping":
        trainer.attach_stop_signal(lambda: flag["stop"])
    server, clients = trainer.init_state(jax.random.key(3))
    with RecompilationSentinel() as s:
        for r in range(3):
            flag["stop"] = bool(r % 2)
            server, clients, metrics = trainer.run_round(server, clients)
            sc = trainer.round_host_scalars(clients, metrics)
            assert sc.get("stop") == (
                float(r % 2) if stop == "flipping" else None)
        for r in range(3):
            flag["stop"] = not flag["stop"]
            again = trainer.round_host_scalars(clients, metrics)
            assert {k: v for k, v in again.items() if k != "stop"} \
                == {k: v for k, v in sc.items() if k != "stop"}
    s.assert_traces(trainer.scalars_trace_name, expected=1)
    s.assert_traces(trainer.trace_name, expected=1)
    assert_same_scalars(
        again, eager_scalars(trainer, clients, metrics,
                             stop=flag["stop"] if stop == "flipping"
                             else None))
    # replicated over the mesh: every device (every process of a pod)
    # holds the one array it fetches
    packed = trainer._scalars_jit(trainer._schedule_dev, clients.epoch,
                                  metrics)
    assert packed.shape == (len(again) - (stop == "flipping"),)
    assert packed.dtype == jnp.float32
    assert packed.sharding.is_fully_replicated
    assert len(packed.sharding.device_set) == trainer.mesh.devices.size


class _BackendCompiles:
    """Backend compiles of the process, by the report of JAX's own that
    ``utils.tracing.CompileSpans`` turns into ``jax.compile`` spans."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        self.funs = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.funs.append(str(kw.get("fun_name", "")))


@pytest.mark.parametrize("stop", [False, True], ids=["nostop", "stop"])
def test_the_fetch_executes_one_program(stop):
    """Every distinct program a fetch executes has to be compiled once
    the caches are dropped, so the compiles of a fetch from cold caches
    count its programs: one, the scalar program, where the eager body
    needs a dozen and more (the control)."""
    trainer = make_trainer()
    if stop:
        trainer.attach_stop_signal(lambda: True)
    server, clients = trainer.init_state(jax.random.key(5))
    for _ in range(2):
        server, clients, metrics = trainer.run_round(server, clients)
        trainer.round_host_scalars(clients, metrics)
    jax.block_until_ready((server.params, clients.epoch))
    jax.clear_caches()
    with _BackendCompiles() as fetch:
        got = trainer.round_host_scalars(clients, metrics)
    assert len(fetch.funs) == 1, fetch.funs
    assert "_round_scalars" in fetch.funs[0]
    # warm, the fetch compiles nothing at all
    with _BackendCompiles() as warm:
        assert trainer.round_host_scalars(clients, metrics) == got
    assert warm.funs == []
    jax.clear_caches()
    with _BackendCompiles() as control:
        want = eager_scalars(trainer, clients, metrics,
                             stop=True if stop else None)
    assert len(control.funs) >= 10, control.funs
    assert_same_scalars(got, want)


def test_skipped_round_metrics_pass_through():
    """The supervisor's stand-in metrics for a skipped round (plain
    zeros, Python defaults for the rest) go through the same program:
    one more trace for their weak types, the same keys."""
    trainer = make_trainer()
    server, clients = trainer.init_state(jax.random.key(1))
    server, clients, metrics = trainer.run_round(server, clients)
    real = trainer.round_host_scalars(clients, metrics)
    z, s = jnp.zeros((trainer.metrics_width,)), jnp.zeros(())
    skipped = RoundMetrics(train_loss=z, train_acc=z, online_mask=z,
                           comm_bytes=s, dropped_clients=s,
                           straggler_clients=s, rejected_updates=s,
                           clipped_updates=s)
    got = trainer.round_host_scalars(clients, skipped)
    assert set(got) == set(real)
    assert_same_scalars(got, eager_scalars(trainer, clients, skipped))
    assert got["mean_epoch"] == real["mean_epoch"]
    assert got["lr"] == real["lr"] and got["n_online"] == 0.0
