"""Round-program builder composition matrix (ISSUE 11).

One parametrized sweep over EVERY (source x dispatch x execution) cell
of ``parallel/round_program.py`` — enumerated from the module's own
axis tuples, so a new axis value can never be silently absent. Each
cell asserts exactly one of:

* **legal** — the cell's per-round trajectory (server params, full
  client state, metrics) is BITWISE-identical to the per-round device
  program with the same execution strategy, and the cell's program
  traces exactly once (the two engine-wide bars); commit cells, whose
  semantics differ from the sync round by design (staleness,
  snapshot bases), instead pin cross-source bitwise parity against
  the resident commit program plus determinism and trace-once;
* **illegal** — ONE ``ValueError`` naming the cell, raised from the
  single validator (construction for round/commit, the ``run_rounds``
  call for scan — the deferred gate).

The chaos/guard composition of the NEW cell (the scanned streamed
program) is pinned here too: chaos + guards ride ``_round_core``, so
the faulted feed x scan trajectory must equal the faulted per-round
device one bitwise.
"""
import re

import jax
import numpy as np
import pytest

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
    MeshConfig, ModelConfig, OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.parallel.round_program import (
    DISPATCHES, EXECUTIONS, SOURCES, cell_name, illegal_reason,
    iter_cells,
)
from fedtorch_tpu.utils.tracing import RecompilationSentinel

CELLS = list(iter_cells())
# the genuinely impossible cells of the base (fedavg) matrix — every
# other combination must run and hold the parity bars
ILLEGAL = {
    ("resident", "commit", "sequential"),
    ("feed", "commit", "sequential"),
}

CHAOS = {"client_drop_rate": 0.3, "straggler_rate": 0.3,
         "nan_inject_rate": 0.3, "guard_updates": True}


def make_cfg(source, *, execution="vmap", sync_mode="sync",
             algorithm="fedavg", fault_kw=None, **fed_kw):
    plane = "stream" if source == "feed" else "device"
    return ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=20,
                        batch_size=16, synthetic_alpha=0.5,
                        synthetic_beta=0.5, data_plane=plane),
        federated=FederatedConfig(
            federated=True, num_clients=12, online_client_rate=0.5,
            algorithm=algorithm, sync_type="local_step",
            sync_mode=sync_mode, **fed_kw),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.3, weight_decay=0.0),
        train=TrainConfig(local_step=3),
        # the sequential fold is one device's
        mesh=MeshConfig(num_devices=1 if execution == "sequential"
                        else None, client_fusion=execution),
        fault=FaultConfig(**(fault_kw or {})),
    ).finalize()


def build_trainer(source, *, execution="vmap", dispatch="round",
                  fault_kw=None, algorithm="fedavg", **fed_kw):
    sync_mode = "async" if dispatch == "commit" else "sync"
    cfg = make_cfg(source, execution=execution, sync_mode=sync_mode,
                   algorithm=algorithm, fault_kw=fault_kw, **fed_kw)
    data = build_federated_data(cfg).train
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    if sync_mode == "async":
        from fedtorch_tpu.async_plane import AsyncFederatedTrainer
        return AsyncFederatedTrainer(cfg, model, make_algorithm(cfg),
                                     data)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data)


def assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def stack_metrics(ms):
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x)
                                              for x in xs]), *ms)


def run_cell(trainer, dispatch, rounds=4, seed=3, chunk=2):
    """Run ``rounds`` rounds/commits through the cell's dispatch and
    return (server, clients, stacked per-round metrics)."""
    server, clients = trainer.init_state(jax.random.key(seed))
    if dispatch == "scan":
        all_ms = []
        for _ in range(rounds // chunk):
            server, clients, ms = trainer.run_rounds(server, clients,
                                                     chunk)
            all_ms.append(jax.tree.map(np.asarray, ms))
        metrics = jax.tree.map(
            lambda *xs: np.concatenate(xs, axis=0), *all_ms)
    else:
        per_round = []
        for _ in range(rounds):
            server, clients, m = trainer.run_round(server, clients)
            per_round.append(m)
        metrics = stack_metrics(per_round)
    trainer.invalidate_stream()
    return server, clients, metrics


def cell_trace_name(trainer, source, dispatch, chunk=2):
    if dispatch == "round":
        return trainer.trace_name if source == "resident" \
            else trainer.stream_trace_name
    if dispatch == "commit":
        return trainer.commit_trace_name if source == "resident" \
            else trainer.commit_stream_trace_name
    suffix = "" if source == "resident" else "_stream"
    return (f"federated.rounds{suffix}"
            f"[{trainer.algorithm.name}]x{chunk}")


@pytest.mark.parametrize("source,dispatch,execution", CELLS)
def test_matrix_cell_parity_or_named_refusal(source, dispatch,
                                             execution):
    cell = (source, dispatch, execution)
    if cell in ILLEGAL:
        with pytest.raises(ValueError,
                           match=re.escape(cell_name(*cell))):
            t = build_trainer(source, execution=execution,
                              dispatch=dispatch)
            if dispatch == "scan":  # deferred gate (never reached here)
                s, c = t.init_state(jax.random.key(0))
                t.run_rounds(s, c, 2)
        return

    trainer = build_trainer(source, execution=execution,
                            dispatch=dispatch)
    with RecompilationSentinel() as sentinel:
        server, clients, metrics = run_cell(trainer, dispatch)
        jax.block_until_ready(jax.tree.leaves(server.params))
    sentinel.assert_traces(
        cell_trace_name(trainer, source, dispatch), expected=1)

    if dispatch == "commit":
        # commit semantics differ from the sync round by design; the
        # bar is cross-source bitwise parity against the resident
        # commit program (the per-commit device program)
        ref = build_trainer("resident", execution=execution,
                            dispatch="commit")
        rs, rc, rm = run_cell(ref, "commit")
        assert_trees_equal((server.params, server.aux, clients),
                           (rs.params, rs.aux, rc))
        assert_trees_equal(metrics, rm)
        return

    # round/scan: bitwise parity with the per-round DEVICE program of
    # the same execution strategy — the engine-wide reference
    ref = build_trainer("resident", execution=execution,
                        dispatch="round")
    rs, rc, rm = run_cell(ref, "round")
    assert_trees_equal((server.params, server.aux, clients),
                       (rs.params, rs.aux, rc))
    assert_trees_equal(metrics, rm)


def test_scanned_stream_composes_with_chaos_and_guards():
    """The NEW cell (feed x scan): chaos crashes/stragglers/poison +
    update guards ride _round_core, so the faulted scanned-stream
    trajectory must equal the faulted per-round device one bitwise."""
    t_ref = build_trainer("resident", fault_kw=CHAOS)
    t_new = build_trainer("feed", fault_kw=CHAOS)
    rs, rc, rm = run_cell(t_ref, "round")
    ss, sc, sm = run_cell(t_new, "scan")
    assert_trees_equal((rs.params, rs.aux, rc), (ss.params, ss.aux, sc))
    assert_trees_equal(rm, sm)
    # the faulted rounds actually exercised the fault path
    assert float(np.sum(np.asarray(sm.dropped_clients))) > 0


def test_run_rounds_refuses_zero_rounds_before_consuming_feeds():
    """run_rounds(.., 0) must refuse BEFORE touching the producer: a
    zero-length scan would trace to an obscure shape error, and on
    the stream plane it would first pop (and lose) a real feed —
    silently desyncing the producer from the device round."""
    t = build_trainer("feed")
    server, clients = t.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="num_rounds"):
        t.run_rounds(server, clients, 0)
    assert t._stream is None  # no producer was started, nothing lost
    # the trainer is still healthy: a real round runs fine after
    server, clients, _ = t.run_round(server, clients)
    t.invalidate_stream()


def test_scan_cell_refused_on_async_at_call_time():
    """The deferred scan gate: an async trainer CONSTRUCTS fine and
    run_rounds raises the one cell-named ValueError at call time —
    commits are host-scheduled events, nothing to scan."""
    t = build_trainer("resident", dispatch="commit")
    server, clients = t.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="run_rounds"):
        t.run_rounds(server, clients, 2)
    with pytest.raises(ValueError, match=re.escape(
            cell_name("resident", "scan", "vmap"))):
        t.run_rounds(server, clients, 2)


@pytest.mark.parametrize("source,dispatch,algorithm,fed_kw,match", [
    # qFFL (shard feed layout) and default-uniform DRFA (host probe
    # plan) now RUN on the feed source — the remaining feed refusal is
    # the lambda-DISTRIBUTED draw, which reads device state (the dual
    # variable) the host feed builder cannot see
    ("feed", "round", "fedavg",
     {"drfa": True, "drfa_lambda_sampling": True}, "participation"),
    ("resident", "commit", "qsparse", {},
     "sync_mode='async' is unsupported"),
    ("feed", "commit", "afl", {},
     "sync_mode='async' is unsupported"),
])
def test_algorithm_precondition_cells_raise_named(source, dispatch,
                                                  algorithm, fed_kw,
                                                  match):
    """Axis-precondition refusals (algorithm families an axis value
    cannot serve) raise the same cell-named ValueError as the
    structural cells — one error site for the whole matrix."""
    with pytest.raises(ValueError) as err:
        build_trainer(source, dispatch=dispatch, algorithm=algorithm,
                      **fed_kw)
    assert re.search(match, str(err.value))
    assert "round-program cell" in str(err.value)


# -- refusal-message snapshots (the gate matrix is user-facing API) -------
# One test per structurally illegal cell pinning the EXACT ValueError
# text, so refusal wording cannot silently regress. The registry-drift
# checker (fedtorch_tpu.lint.registry_audit, FTC005) requires each
# illegal cell's name to appear here next to the ILLEGAL set.

def _validate(source, dispatch, execution, sync_mode):
    cfg = make_cfg(source, execution=execution, sync_mode=sync_mode)
    alg = make_algorithm(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    from fedtorch_tpu.parallel.round_program import validate_cell
    validate_cell(source, dispatch, execution, cfg=cfg, algorithm=alg,
                  model=model, mesh_devices=1, k_online=2,
                  gather_mode="auto", has_val=False)


_COMMIT_SEQUENTIAL_REASON = (
    "mesh.client_fusion='sequential' runs the cohort one client after "
    "another into a running weighted sum and keeps no per-client copy "
    "of the parameters: buffered commits train each job against its "
    "own stale snapshot from the ring")


def test_refusal_snapshot_resident_commit_sequential():
    with pytest.raises(ValueError) as err:
        _validate("resident", "commit", "sequential", "async")
    assert str(err.value) == (
        "round-program cell (resident x commit x sequential) is "
        "unsupported here: " + _COMMIT_SEQUENTIAL_REASON)


def test_refusal_snapshot_feed_commit_sequential():
    with pytest.raises(ValueError) as err:
        _validate("feed", "commit", "sequential", "async")
    assert str(err.value) == (
        "round-program cell (feed x commit x sequential) is "
        "unsupported here: " + _COMMIT_SEQUENTIAL_REASON)


def test_refusal_snapshot_scan_under_async():
    """The deferred scan gate's exact text (run_rounds on the async
    plane) — structurally impossible like the sequential commits, but
    refused at call time rather than construction."""
    with pytest.raises(ValueError) as err:
        _validate("resident", "scan", "vmap", "async")
    assert str(err.value) == (
        "round-program cell (resident x scan x vmap) is unsupported "
        "here: run_rounds scans ONE traced round program over R "
        "rounds' inputs, but async commits are host-scheduled events "
        "(each commit's jobs come from the event scheduler), so no "
        "R-commit program exists to scan — call run_round once per "
        "commit, or use --sync_mode sync for the scan dispatch")


def test_matrix_has_no_silently_absent_cells():
    """Every combination of the module's axis tuples is either in this
    file's ILLEGAL set (and refused by the validator) or reaches a
    runnable program — the parametrization above covers the full
    product, and the validator agrees with ILLEGAL on the base
    config."""
    assert len(CELLS) == len(SOURCES) * len(DISPATCHES) * len(EXECUTIONS)
    for source, dispatch, execution in CELLS:
        sync_mode = "async" if dispatch == "commit" else "sync"
        cfg = make_cfg(source, execution=execution, sync_mode=sync_mode)
        alg = make_algorithm(cfg)
        model = define_model(cfg, batch_size=cfg.data.batch_size)
        reason = illegal_reason(
            source, dispatch, execution, cfg=cfg, algorithm=alg,
            model=model, mesh_devices=1, k_online=2,
            gather_mode="auto", has_val=False)
        expected_illegal = (source, dispatch, execution) in ILLEGAL
        assert (reason is not None) == expected_illegal, (
            (source, dispatch, execution), reason)
