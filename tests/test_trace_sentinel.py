"""Recompilation sentinel: the round program traces exactly once.

The dynamic half of the tracing-hazard gate (static half:
fedtorch_tpu.lint, tests/test_lint_*.py).  PR 1's chaos/guard
machinery and the bench path both rest on "static config => unchanged
traced program"; these tests make that contract executable: the FedAvg
and SCAFFOLD round functions must trace exactly once across multiple
rounds — fault-free AND under a chaos+guard schedule — and any future
change that sneaks a retrace into the hot loop fails here.
"""
import jax
import pytest

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
    ModelConfig, OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.utils import (
    RecompilationSentinel, instrument_trace, jit_cache_size,
)


def make_trainer(algorithm="fedavg", fault_kw=None, num_clients=8):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=10,
                        batch_size=16, synthetic_alpha=0.5,
                        synthetic_beta=0.5),
        federated=FederatedConfig(
            federated=True, num_clients=num_clients, num_comms=5,
            online_client_rate=0.5, algorithm=algorithm,
            sync_type="local_step"),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.05, weight_decay=0.0),
        train=TrainConfig(local_step=3),
        fault=FaultConfig(**(fault_kw or {})),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    alg = make_algorithm(cfg)
    return FederatedTrainer(cfg, model, alg, data.train)


@pytest.mark.parametrize("algorithm", ["fedavg", "scaffold"])
def test_round_traces_exactly_once(algorithm):
    """3+ rounds of the hot path: ONE trace, ONE compiled program."""
    trainer = make_trainer(algorithm)
    server, clients = trainer.init_state(jax.random.key(0))
    with RecompilationSentinel() as s:
        for _ in range(3):
            server, clients, _ = trainer.run_round(server, clients)
        # by round 3 every input is a committed device-resident
        # donated output; the executable cache must stop growing
        # (the first rounds add a fresh-input vs steady-state entry
        # pair without retracing — the jaxpr is reused)
        cache_steady = jit_cache_size(trainer._round_jit)
        for _ in range(2):
            server, clients, metrics = trainer.run_round(server, clients)
        jax.block_until_ready(server.params)
    s.assert_traces(trainer.trace_name, expected=1)
    assert s.count(f"federated.round[{algorithm}]") == 1
    cache_end = jit_cache_size(trainer._round_jit)
    assert cache_end == cache_steady  # None == None when unavailable


@pytest.mark.parametrize("algorithm", ["fedavg", "scaffold"])
def test_round_traces_once_under_faults(algorithm):
    """Chaos + guards are static config: the faulted round program
    must also trace exactly once across rounds — the contract the
    robustness layer (PR 1) depends on."""
    trainer = make_trainer(algorithm, fault_kw=dict(
        client_drop_rate=0.25, straggler_rate=0.25,
        straggler_step_frac=0.5, nan_inject_rate=0.25,
        guard_updates=True))
    server, clients = trainer.init_state(jax.random.key(1))
    with RecompilationSentinel() as s:
        for _ in range(3):
            server, clients, metrics = trainer.run_round(server, clients)
        jax.block_until_ready(server.params)
    s.assert_traces(trainer.trace_name, expected=1)


def test_round_traces_once_with_lifecycle_armed():
    """Process lifecycle (ISSUE 4) is host-only: with the stall
    watchdog armed AND a stop signal folded into the per-round scalar
    fetch, the round program still traces exactly once — the 'zero
    overhead when off, host-only when on' contract (the static half —
    byte-identical HLO — is pinned by test_preemption.py)."""
    from fedtorch_tpu.robustness import StallWatchdog

    trainer = make_trainer(
        "fedavg", fault_kw=dict(watchdog_timeout_s=60.0))
    trainer.attach_stop_signal(lambda: False)
    server, clients = trainer.init_state(jax.random.key(3))
    with StallWatchdog(60.0, exit_fn=lambda code: None) as wd:
        with RecompilationSentinel() as s:
            for r in range(3):
                server, clients, metrics = trainer.run_round(
                    server, clients)
                sc = trainer.round_host_scalars(clients, metrics)
                assert sc["stop"] == 0.0
                wd.heartbeat(r)
    s.assert_traces(trainer.trace_name, expected=1)
    assert not wd.fired


def test_sentinel_catches_retraces():
    """Positive control: the sentinel machinery itself must see a
    retrace when one genuinely happens (new shape => new trace)."""
    import jax.numpy as jnp

    @jax.jit
    @instrument_trace("sentinel_test.f")
    def f(x):
        return jnp.sum(x * 2)

    with RecompilationSentinel() as s:
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))      # cached: no retrace
        f(jnp.ones((8,)))      # new shape: retrace
    assert s.count("sentinel_test.f") == 2
    with pytest.raises(AssertionError, match="traced 2x"):
        s.assert_traces("sentinel_test.f", expected=1)


def test_sentinel_scoping_and_nesting():
    """Counts are scoped to the context: events before/after the
    block are invisible, and sentinels nest independently."""
    import jax.numpy as jnp

    @jax.jit
    @instrument_trace("sentinel_test.g")
    def g(x):
        return x + 1

    g(jnp.ones((2,)))  # traced outside any sentinel
    with RecompilationSentinel() as outer:
        g(jnp.ones((2,)))  # cached — no event
        with RecompilationSentinel() as inner:
            g(jnp.ones((3,)))  # retrace — seen by both
        g(jnp.ones((5,)))      # retrace — seen by outer only
    assert inner.count("sentinel_test.g") == 1
    assert outer.count("sentinel_test.g") == 2


def test_run_rounds_scan_driver_traces_once():
    """The multi-round lax.scan driver is its own single-trace
    program (and does not re-trace the per-round program)."""
    trainer = make_trainer("fedavg")
    server, clients = trainer.init_state(jax.random.key(2))
    with RecompilationSentinel() as s:
        server, clients, ms = trainer.run_rounds(server, clients, 3)
        jax.block_until_ready(server.params)
        server, clients, ms = trainer.run_rounds(server, clients, 3)
        jax.block_until_ready(server.params)
    assert s.count("federated.rounds[fedavg]x3") == 1
    # the scan body inlines round_fn directly — the per-round jit
    # entry must not have been traced at all by the scan driver
    assert s.count(trainer.trace_name) == 0


# -- stage scopes inside the round program ---------------------------------
# The trace reducers (benchmark/harness/stage_reduce.py) and the
# documents name these; a stage that loses its scope reads as unstaged
# time on the chip, so the lowered program is held to the list here.
# (fed.pre_round and fed.guard hold no operation in a fault-free FedAvg
# or SCAFFOLD round; the two cases after the matrix arm them.)
BASE_STAGES = ("fed.select", "fed.gather", "fed.local_steps",
               "fed.augment", "fed.forward_backward", "fed.opt_step",
               "fed.wire", "fed.aggregate", "fed.server_step",
               "fed.scatter", "fed.metrics")


def make_image_trainer(algorithm, fusion, plane, fault_kw=None):
    """A CIFAR-shaped CNN trainer (augmentation needs images), small
    enough that lowering takes a second or two."""
    import numpy as np

    from fedtorch_tpu.config import MeshConfig
    from fedtorch_tpu.data.batching import stack_partitions

    sizes = (24, 9, 17, 24)
    cfg = ExperimentConfig(
        data=DataConfig(dataset="cifar10", batch_size=6, augment=True,
                        data_plane=plane),
        federated=FederatedConfig(
            federated=True, num_clients=len(sizes),
            online_client_rate=0.5, algorithm=algorithm,
            sync_type="local_step"),
        model=ModelConfig(arch="cnn", norm="bn"),
        # the sequential fold carries no local momentum buffer
        optim=OptimConfig(lr=0.05, in_momentum=fusion != "sequential"),
        train=TrainConfig(local_step=2),
        mesh=MeshConfig(num_devices=1, client_fusion=fusion),
        fault=FaultConfig(**(fault_kw or {})),
    ).finalize()
    rng = np.random.RandomState(0)
    feats = rng.randn(sum(sizes), 32, 32, 3).astype(np.float32)
    labels = rng.randint(0, 10, sum(sizes))
    off = np.concatenate([[0], np.cumsum(sizes)])
    parts = [np.arange(off[i], off[i + 1]) for i in range(len(sizes))]
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    return FederatedTrainer(cfg, model, make_algorithm(cfg),
                            stack_partitions(feats, labels, parts))


def lowered_round_text(trainer):
    """The round program's StableHLO with its locations (where the
    scopes live), lowered against abstract state: nothing executes."""
    server, clients = jax.eval_shape(trainer.init_state,
                                     jax.random.key(0))
    try:
        programs, primary = trainer.lowered_cost_programs(server, clients)
        return programs[primary].as_text(debug_info=True)
    finally:
        trainer.invalidate_stream()


@pytest.mark.parametrize("plane", ["device", "stream"])
@pytest.mark.parametrize("algorithm,fusion", [
    ("fedavg", "vmap"), ("scaffold", "vmap"), ("fedavg", "sequential")])
def test_round_program_carries_every_stage_name(algorithm, fusion, plane):
    texts = []
    for _ in range(2):   # one call site: locations are part of the text
        trainer = make_image_trainer(algorithm, fusion, plane)
        assert trainer.client_fusion == fusion
        texts.append(lowered_round_text(trainer))
    # the sequential round shapes and sums a client's upload inside
    # its fold, and FedAvg's aggregate hook adds no operation to it
    stages = [s.replace("fed.wire", "fed.fold") for s in BASE_STAGES
              if s != "fed.aggregate"] \
        if fusion == "sequential" else BASE_STAGES
    missing = [s for s in stages if s not in texts[0]]
    assert not missing, f"stages without a scope in the program: {missing}"
    # a scope is metadata of the program, never a function of the
    # round or of the build: the same cell lowers to the same text
    # (compared as a bool: a diff of two 300 KB texts takes minutes)
    assert (texts[0] == texts[1]) is True


def test_guard_stage_is_named_when_faults_are_armed():
    trainer = make_image_trainer("fedavg", "vmap", "device", fault_kw=dict(
        client_drop_rate=0.25, nan_inject_rate=0.25, guard_updates=True))
    assert "fed.guard" in lowered_round_text(trainer)


def test_pre_round_stage_is_named_when_the_algorithm_has_the_hook():
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=10,
                        batch_size=16),
        federated=FederatedConfig(
            federated=True, num_clients=8, online_client_rate=0.5,
            algorithm="apfl", sync_type="local_step", personal=True,
            adaptive_alpha=True),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.05, weight_decay=0.0),
        train=TrainConfig(local_step=3),
    ).finalize()
    data = build_federated_data(cfg)
    trainer = FederatedTrainer(
        cfg, define_model(cfg, batch_size=cfg.data.batch_size),
        make_algorithm(cfg), data.train, val_data=data.val)
    assert "fed.pre_round" in lowered_round_text(trainer)


def test_eval_program_carries_its_stage_name():
    import numpy as np

    from fedtorch_tpu.parallel.evaluate import lowered_eval_program

    trainer = make_trainer("fedavg")
    server, _ = jax.eval_shape(trainer.init_state, jax.random.key(0))
    x = np.zeros((20, 10), np.float32)
    y = np.zeros((20,), np.int32)
    text = lowered_eval_program(trainer.model, server.params, x,
                                y).as_text(debug_info=True)
    assert "eval.forward" in text
