"""The asynchronous buffered federation plane (FedBuff-style server).

``cfg.federated.sync_mode='async'`` replaces the blocking round with a
COMMIT loop (Nguyen et al., arXiv:2106.06639; FedScale's async mode,
Lai et al. 2022): ``concurrency`` clients are always training, each
against the server snapshot current at its dispatch; the server folds
finished updates into a buffer of ``m = async_buffer_size`` and commits
when it fills — so the commit clock follows the FASTEST m arrivals and
a straggler delays only itself, not the round.

Execution shape (everything trace-once and deterministic):

* **Event schedule** (:mod:`.scheduler`): completion order is a pure
  function of (seed, commit) — threefry-derived delays reusing the
  chaos subsystem's straggler knobs. No update is materialized before
  its commit; the jitted COMMIT PROGRAM computes all m buffered local
  trainings at once, each against its own snapshot.
* **Snapshot ring**: ``server.aux`` is wrapped as ``{'alg': <algorithm
  aux>, 'ring': {'params', 'aux'}}`` — the last ``snapshot_ring``
  committed (params, server-aux) versions as stacked [R] trees, indexed
  in-program by each job's dispatch version. The wrap rides the
  existing checkpoint path, which is what makes a preempted async run
  resumable bitwise (tests/test_preemption.py).
* **Staleness weighting** (:mod:`.staleness`): each update's
  aggregation weight is damped by s(commit - version) and the composed
  weights flow through the guard renormalization
  (robustness/guards.py) — a rejected stale update hands back exactly
  its damped weight.
* **Commit program** = the sync engine's ``_round_core`` re-dispatched
  through its commit seam (parallel/federated.py): per-job base
  params/aux threaded through every local hook — SCAFFOLD's control
  step ``g + c - c_i`` and its control update both read the STALE
  server control the client actually trained against, which is the
  stale-snapshot correction async SCAFFOLD needs — then guards,
  renormalization, server step against the CURRENT params, and the
  ring rotates.

Algorithm gate: FedAvg/FedProx/FedAdam (server-side adaptivity) and
SCAFFOLD are wired; families whose hooks read global round structure
the buffer breaks (AFL/qFFL losses over the full cohort, DRFA's dual
phase and lambda participation, the personalized families' val
streams, qsparse's post-round tracking variate) raise a single
ValueError at construction naming the commit cell — the refusals live
in ``parallel/round_program.py`` with the rest of the composition
matrix, never deep in tracing. The commit PROGRAM itself is built
there too (the one-step member of the round-program family); this
module owns only the host side: the event scheduler, the snapshot-ring
state wrap, and the commit-keyed feed producer.
"""
from __future__ import annotations

import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedtorch_tpu.algorithms.base import FedAlgorithm
from fedtorch_tpu.async_plane.scheduler import AsyncSchedule
from fedtorch_tpu.config import ExperimentConfig
from fedtorch_tpu.core.state import tree_broadcast_clients
from fedtorch_tpu.data.batching import ClientData, round_row_plan
from fedtorch_tpu.data.streaming import (
    StreamFeedProducer, _cpu_device, _cpu_scope,
)
from fedtorch_tpu.models.common import ModelDef
from fedtorch_tpu.parallel.federated import (
    FederatedTrainer, podscale_feed_placer,
)
from fedtorch_tpu.parallel.mesh import local_cohort_rows, replicate
from fedtorch_tpu.parallel.round_program import (
    ASYNC_ALGORITHMS, ASYNC_TRAIN_SALT, CommitJobs,
)
from fedtorch_tpu.robustness.availability import make_availability_model
from fedtorch_tpu.utils.tracing import instrument_trace

__all__ = ["ASYNC_ALGORITHMS", "AsyncFederatedTrainer", "CommitJobs"]


def _gate(why: str) -> ValueError:
    """Host-scheduler feasibility refusals (buffer/population sizing);
    the composition-matrix gates live in round_program.validate_cell."""
    return ValueError(
        f"sync_mode='async' is unsupported here: {why}; "
        "use --sync_mode sync")


class _AsyncRowPlan:
    """Host replica of the commit program's row plan (the async twin of
    ``data.streaming.RoundSchedule``): given the dispatch ids and
    client ids of one commit, reproduces EXACTLY the per-job training
    rngs (``fold_in(server.rng, ASYNC_TRAIN_SALT)`` then the dispatch
    fold) and ``round_row_plan`` rows the device commit program derives
    — threefry is backend-deterministic, so the CPU replay is
    bit-exact."""

    def __init__(self, key_data, key_impl, n_max: int, num_rows: int,
                 sizes: np.ndarray):
        self._cpu = _cpu_device()
        sizes = np.asarray(sizes, np.int32)

        def rows_fn(key, dispatch, idx):
            rngs = jax.vmap(lambda d: jax.random.fold_in(
                jax.random.fold_in(key, ASYNC_TRAIN_SALT), d))(dispatch)
            on_sizes = jnp.take(jnp.asarray(sizes), idx)
            return jax.vmap(lambda r, s: round_row_plan(
                r, s, n_max, num_rows))(rngs, on_sizes)

        with self._scope():
            self._key = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(key_data)), impl=key_impl)
            # the key input is reused by every commit's replay
            # lint: disable=FTL004 — key reused every commit
            self._jit = jax.jit(rows_fn)

    def _scope(self):
        return _cpu_scope(self._cpu)

    def __call__(self, dispatch: np.ndarray, idx: np.ndarray):
        with self._scope():
            rows = self._jit(self._key,
                             np.asarray(dispatch, np.int32),
                             np.asarray(idx, np.int32))
            return np.asarray(jax.device_get(rows))


class AsyncFederatedTrainer(FederatedTrainer):
    """Drop-in trainer for ``sync_mode='async'``: :meth:`run_round`
    executes one COMMIT (``server.round`` counts commit versions, so
    the CLI round loop, checkpointing, eval cadence, preemption drain
    and the supervisor all work unchanged)."""

    supports_async = True
    # run_round serves the COMMIT dispatch: the base constructor
    # validates the (source x commit x execution) cell — algorithm,
    # val-stream and shard-gather refusals all ride the one
    # validator in parallel/round_program.py
    construction_dispatch = "commit"

    def __init__(self, cfg: ExperimentConfig, model: ModelDef,
                 algorithm: FedAlgorithm, data: ClientData,
                 val_data=None, mesh=None, gather_mode: str = "auto"):
        fed = cfg.federated
        k_online = max(int(fed.online_client_rate * data.num_clients), 1)
        self.concurrency = fed.async_concurrency or k_online
        self.buffer_size = fed.async_buffer_size or max(
            1, self.concurrency // 2)
        if self.buffer_size > self.concurrency:
            raise _gate(
                f"async_buffer_size ({self.buffer_size}) exceeds the "
                f"in-flight concurrency ({self.concurrency}) — a commit "
                "could never fill")
        if data.num_clients < self.concurrency + self.buffer_size:
            raise _gate(
                f"num_clients ({data.num_clients}) must be >= "
                f"concurrency + buffer ({self.concurrency} + "
                f"{self.buffer_size}) so every arrival has a distinct "
                "replacement to dispatch")
        self.snapshot_ring = fed.snapshot_ring

        super().__init__(cfg, model, algorithm, data, val_data=val_data,
                         mesh=mesh, gather_mode=gather_mode)

        # commits always consume packed rows (round_row_plan order)
        self.gather_mode = "batch"
        # async stragglers are arrival DELAYS (the scheduler), not step
        # cuts — the freeze mask is epoch-sync-only here
        self.mask_steps = self.epoch_sync

        self._sched: Optional[AsyncSchedule] = None
        # the commit programs come from the round-program builder (the
        # degenerate one-step scan of the family) — no commit-specific
        # device code lives in this module anymore
        self.commit_trace_name = \
            f"federated.commit[{algorithm.name}]"
        self._commit_jit = jax.jit(
            instrument_trace(self.commit_trace_name,
                             self.programs.build("commit")),
            donate_argnums=(0, 1)) \
            if self.data_plane == "device" else None
        self.commit_stream_trace_name = \
            f"federated.commit_stream[{algorithm.name}]"
        self._commit_stream_jit = jax.jit(
            instrument_trace(self.commit_stream_trace_name,
                             self.programs.build("commit")),
            donate_argnums=(0, 1)) \
            if self.data_plane == "stream" else None
        # last scheduler's staleness histogram, preserved across
        # invalidate_stream teardowns so run-end/drain telemetry can
        # still emit it (the CLI's finally reads it AFTER the stream
        # teardown; a rebuilt scheduler's fast-forward replays every
        # commit, so a later live histogram supersedes the stash)
        self._hist_stash: Optional[dict] = None

    @property
    def metrics_width(self) -> int:
        """Sparse-mode commits emit [m]-wide cohort metrics — the m
        buffered jobs ARE the commit's cohort (perm keeps [C])."""
        return self.buffer_size if self.participation_mode == "sparse" \
            else self.num_clients

    # -- state -----------------------------------------------------------
    def init_state(self, rng: jax.Array):
        """Sync init, then wrap the server aux with the snapshot ring:
        every slot starts as version 0 (the init params/aux), which is
        exactly what the initial in-flight cohort trains against."""
        server, clients = super().init_state(rng)
        R = self.snapshot_ring
        ring = {"params": tree_broadcast_clients(server.params, R),
                "aux": tree_broadcast_clients(server.aux, R)}
        server = server._replace(aux={"alg": server.aux, "ring": ring})
        return replicate(server, self.mesh), clients

    # -- host-side commit loop -------------------------------------------
    def _schedule_args(self) -> dict:
        flt = self.fault
        return dict(
            num_clients=self.num_clients, concurrency=self.concurrency,
            buffer_size=self.buffer_size, ring_size=self.snapshot_ring,
            # 'sparse' keeps selection O(1) per dispatch at
            # million-client populations (scheduler rejection draw)
            participation_mode=self.participation_mode,
            straggler_rate=flt.straggler_rate,
            straggler_step_frac=flt.straggler_step_frac,
            # the arrival model (robustness/availability.py): the
            # default reproduces the legacy draws bitwise, 'trace'
            # arms device classes + diurnal dropout. Built fresh per
            # schedule so a rebuilt scheduler replays identically.
            model=make_availability_model(flt))

    def _server_key_state(self, server):
        """One batched fetch of (raw key data, commit) — paid only at
        (re)start, exactly like the sync stream plane's resync."""
        key_data, round0 = jax.device_get(
            (jax.random.key_data(server.rng), server.round))
        return key_data, jax.random.key_impl(server.rng), int(round0)

    def _ensure_schedule(self, server) -> None:
        if self._sched is not None:
            return
        key_data, key_impl, commit0 = self._server_key_state(server)
        self._sched = AsyncSchedule(key_data, key_impl,
                                    start_commit=commit0,
                                    **self._schedule_args())

    def _ensure_async_stream(self, server) -> None:
        if self._stream is not None:
            return
        key_data, key_impl, commit0 = self._server_key_state(server)
        sched = AsyncSchedule(key_data, key_impl, start_commit=commit0,
                              **self._schedule_args())
        # visible to schedule_stats / commit_times consumers on this
        # plane too; the producer
        # thread owns the simulation, so counters may run up to the
        # prefetch depth AHEAD of the last consumed commit
        self._sched = sched
        rows_fn = _AsyncRowPlan(
            key_data, key_impl, self.host_store.n_max,
            self.local_steps * self.batch_size, self.host_store.sizes)

        def plan_fn(step: int):
            plan = sched.next_commit()
            rows = rows_fn(plan.dispatch, plan.idx)
            jobs = CommitJobs(idx=plan.idx, version=plan.version,
                              dispatch=plan.dispatch,
                              straggler=plan.straggler)
            return plan.commit, plan.idx, rows, jobs

        # plan_fn must not close over self (producer-thread leak guard,
        # see FederatedTrainer._next_stream_feed)
        mesh = self.mesh
        if self.podscale_armed:
            # pod-scale commit plane: the m-wide buffer is the commit's
            # cohort — each host packs only its m/S block and the
            # placer assembles the cohort-sharded device feed (the
            # CommitJobs extras ride along replicated)
            place = podscale_feed_placer(mesh, self.buffer_size)
            cohort_rows = local_cohort_rows(mesh, self.buffer_size,
                                            self.client_shards)
        else:
            place = lambda t: replicate(t, mesh)  # noqa: E731
            cohort_rows = None
        self._stream = StreamFeedProducer(
            self.host_store, batch_size=self.batch_size,
            start_round=commit0, plan_fn=plan_fn,
            place_fn=place, cohort_rows=cohort_rows)
        self._stream_finalizer = weakref.finalize(
            self, StreamFeedProducer.close, self._stream)

    def run_round(self, server, clients):
        """One COMMIT: pop the scheduler's next m arrivals, run the
        commit program. Sequential-consumption contract and
        :meth:`invalidate_stream` resync semantics are the stream
        plane's (the scheduler replays from the live device state on
        (re)start, so supervisor rollback/reseed, checkpoint resume and
        the CLI drain all work unchanged)."""
        if self.data_plane == "stream":
            def pop():
                # re-ensures after an invalidate_stream teardown: the
                # rebuild wrapper's contract is that pop reconstructs
                # the producer (and the event scheduler with it) from
                # the live device state
                self._ensure_async_stream(server)
                return self._stream.next_feed()
            feed, jobs = self._pop_stream_with_rebuild(pop)
            return self._commit_stream_jit(server, clients, jobs, feed)
        self._ensure_schedule(server)
        plan = self._sched.next_commit()
        jobs = CommitJobs(idx=plan.idx, version=plan.version,
                          dispatch=plan.dispatch,
                          straggler=plan.straggler)
        return self._commit_jit(server, clients, jobs, self.data)

    # NOTE: run_rounds is NOT overridden — the base method's scan-cell
    # validation (parallel/round_program.py) raises the one cell-named
    # ValueError at call time: async commits are host-scheduled events,
    # so no R-commit program exists for run_rounds to scan.

    def lowered_cost_programs(self, server, clients,
                              num_scan_rounds: int = 0):
        """The async twin of the base trainer's cost-capture handles:
        the COMMIT program (per data plane) from the round-program
        builder, lowered uninstrumented against abstract [m] job
        inputs — no scheduler state is consumed and the sentinel sees
        nothing. ``num_scan_rounds`` is ignored (the scan cell is
        refused on this plane)."""
        m = self.buffer_size
        sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
        jobs = CommitJobs(idx=sds((m,), jnp.int32),
                          version=sds((m,), jnp.int32),
                          dispatch=sds((m,), jnp.int32),
                          straggler=sds((m,), jnp.float32))
        commit_fn = self.programs.build("commit")
        if self.data_plane == "stream":
            primary = "commit_stream"
            lowered = jax.jit(
                commit_fn, donate_argnums=(0, 1)).lower(
                server, clients, jobs, self._feed_struct(k=m))
        else:
            primary = "commit"
            lowered = jax.jit(
                commit_fn, donate_argnums=(0, 1)).lower(
                server, clients, jobs, self.data)
        return {primary: lowered}, primary

    def invalidate_stream(self) -> None:
        """Also drop the event scheduler: any rewrite of host-visible
        training state (supervisor rollback/reseed, resume, drain)
        desyncs the replay; the next commit re-syncs from the live
        (rng, round) device state. The staleness histogram is stashed
        first — it is pure telemetry over ALREADY-committed updates,
        so it survives the teardown unchanged."""
        if self._sched is not None and self._sched.staleness_hist:
            self._hist_stash = dict(self._sched.staleness_hist)
        super().invalidate_stream()
        self._sched = None

    @property
    def schedule_stats(self):
        """Scheduler counters (dispatches/stragglers/ring clamps) —
        None before the first commit."""
        return self._sched.stats if self._sched is not None else None

    def telemetry_gauges(self) -> dict:
        """Stream gauges (when on that plane) plus the async commit
        plane's: buffer occupancy, scheduler dispatch/straggler/ring-
        clamp counters, and the commit rate in virtual time units
        (commits so far / last commit's virtual clock, comparable with
        the sync round clock). All host counters; zero device syncs."""
        out = super().telemetry_gauges()
        sched = self._sched
        if sched is None:
            return out
        st = sched.stats
        ct = sched.commit_times
        out.update({
            "async_dispatches": float(st.dispatches),
            "async_stragglers": float(st.stragglers),
            "async_ring_clamped": float(st.staleness_clamped),
            "async_dropouts": float(st.dropouts),
            "async_buffer": float(self.buffer_size),
            "async_commit_rate": (len(ct) / ct[-1])
            if ct and ct[-1] > 0 else 0.0,
        })
        return out

    def staleness_histogram(self):
        """{commits-stale: count} over every committed update so far
        (post ring-clamp) — emitted as ``events.jsonl`` snapshot
        records (drain path, debug cadence, run end) rather than
        per-row (it is a dict, not a scalar gauge). Falls back to the
        pre-``invalidate_stream`` stash so the run-end emission — which
        runs after the stream teardown — still sees it."""
        if self._sched is not None and self._sched.staleness_hist:
            return dict(self._sched.staleness_hist)
        return dict(self._hist_stash) if self._hist_stash else None
