"""The round-program builder: one program family over three orthogonal
axes (ROADMAP item 2 — "one round-program compiler").

What used to be four hand-maintained dispatch paths in
``parallel/federated.py`` (per-round device, ``run_rounds`` scan,
streamed per-round, async commit) plus a pairwise gate matrix (stream
refused ``run_rounds``, async refused scan/shard-gather) is composed
here from three independent choices:

* **data source** — ``'resident'`` (the full ``[C, n_max, ...]`` client
  store lives in HBM and the round gathers its rows in-program) or
  ``'feed'`` (the store stays host-resident and the program consumes a
  host-packed, double-buffered feed — ``data/streaming.py``);
* **dispatch** — ``'round'`` (one device call per communication round),
  ``'scan'`` (R rounds under one ``lax.scan`` — the 47–266× dispatch
  lever), or ``'commit'`` (the async plane's one-step buffered commit
  over snapshot-ring inputs — the degenerate length-1 member of the
  scan family, with per-job stale bases threaded through the commit
  seam of ``_round_core``);
* **client execution** — ``'vmap'`` (per-client model compute under
  ``vmap``) or ``'sequential'`` (the cohort
  one client after another into a running weighted sum, no per-client
  copy of the parameters at rest —
  ``FederatedTrainer._round_core_sequential``; what needs the stacked
  cohort is refused by :func:`_sequential_refusal`).

Every cell funnels into the SAME ``FederatedTrainer._round_core``, so
the robust-aggregation seam, chaos/guard masks, staleness weights and
the host-recovery rebuild compose identically everywhere, and every
legal cell holds the two engine-wide bars: bitwise parity of the
per-round trajectory with the per-round device program, and exactly
one trace per program (``tests/test_round_builder.py``).

The gate matrix now contains only the cells that are genuinely
impossible, each refused by ONE named ``ValueError`` from
:func:`validate_cell` — there are no per-path gate checks left in
``parallel/federated.py`` or ``async_plane/commit.py``:

* ``scan`` under ``sync_mode='async'`` — commits are host-scheduled
  events (the event scheduler decides each commit's jobs), so there is
  no R-commit program for one trace to scan;
* algorithm/feature preconditions of an axis value (a ``feed`` source
  cannot replay server-state-dependent participation; ``commit`` needs
  a stale-snapshot-safe algorithm; ``sequential`` serves what needs no
  stacked cohort) — named with the same reasons the old per-path gates
  carried.

The pod-scale **client-shard fact** (``mesh.client_shards``,
docs/performance.md "Pod-scale round programs") composes with every
axis: the round's k online clients split into S contiguous blocks
over a 2-D ``[S, devices/S]`` mesh, and the aggregation seam reduces
them with the S-invariant hierarchical sum
(``parallel/podscale.py``) — exactly ONE cross-shard all-reduce per
round/commit program, certified by the FTP004 budget. Compositions
whose cross-client float reductions live OUTSIDE that seam (robust
rules, cohort statistics, cohort-global-loss algorithms, per-client
val streams) are refused by name here rather than silently losing
bitwise parity.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from fedtorch_tpu.algorithms.base import FedAlgorithm
from fedtorch_tpu.data.batching import gather_client_rows, round_row_plan

# the three axes; tests and the chaos-suite matrix enumerate these so a
# new axis value can never be silently absent from the coverage matrix
SOURCES = ("resident", "feed")
DISPATCHES = ("round", "scan", "commit")
EXECUTIONS = ("vmap", "sequential")

# algorithms the sequential execution serves: their client hooks are
# the base's (a weighted delta as payload, no per-client aux read
# back), so a client's result can be folded into the running sum and
# let go of
SEQUENTIAL_ALGORITHMS = ("fedavg", "fedprox", "fedadam")

# algorithms wired for stale-snapshot commits (the commit dispatch):
# their hooks read only the per-job base params/aux the snapshot ring
# threads, never cohort-global round structure
ASYNC_ALGORITHMS = ("fedavg", "fedprox", "fedadam", "scaffold")

# fold constant separating the commit program's per-dispatch training
# streams from the round streams (chaos_salt 0x7FFFFFFD and the
# augmentation parent 0x7FFFFFFF are taken; < 2^31 so fold_in accepts
# it). Defined here — with the program family whose PRNG contract it
# is — and re-exported by async_plane/scheduler.py.
ASYNC_TRAIN_SALT = 0x7FFFFFF9


class CommitJobs(NamedTuple):
    """One commit's buffered updates as device inputs (all [m])."""
    idx: jnp.ndarray        # int32 client ids (distinct)
    version: jnp.ndarray    # int32 snapshot version each trained on
    dispatch: jnp.ndarray   # int32 global dispatch counter (rng fold)
    straggler: jnp.ndarray  # float32 {0,1} tail-delay dispatches


def cell_name(source: str, dispatch: str, execution: str) -> str:
    return f"({source} x {dispatch} x {execution})"


def iter_cells():
    """Every (source, dispatch, execution) combination — the coverage
    matrix ``tests/test_round_builder.py`` parametrizes over."""
    for source in SOURCES:
        for dispatch in DISPATCHES:
            for execution in EXECUTIONS:
                yield source, dispatch, execution


def cell_build_facts(source: str, dispatch: str, execution: str, *,
                     client_shards: int = 0) -> dict:
    """How a trainer serving this cell is configured — the config
    axes a cell name maps onto. The enumeration hook the program
    auditor (``lint/program_audit.py``) and future matrix drivers
    build trainers from, so cell-to-config mapping lives with the
    axes instead of being re-derived per caller. ``client_shards``
    threads the pod-scale cohort-shard fact through unchanged (0 =
    legacy, S > 1 = the sharded variant of the same cell)."""
    if source not in SOURCES or dispatch not in DISPATCHES \
            or execution not in EXECUTIONS:
        raise ValueError(
            f"unknown round-program cell "
            f"{cell_name(source, dispatch, execution)}")
    return {
        "data_plane": "stream" if source == "feed" else "device",
        "sync_mode": "async" if dispatch == "commit" else "sync",
        "client_fusion": execution,
        "client_shards": client_shards,
    }


def collective_budget(source: str, dispatch: str, execution: str, *,
                      mesh_devices: int, num_rounds: int = 1,
                      client_shards: int = 0) -> int:
    """Max cross-device collectives the cell's lowered program may
    carry — the FTP004 budget (``lint/program_audit.py``).

    Every cell funnels into the one ``_round_core`` aggregation, so
    the budget is ONE collective per round (the masked psum-style
    weighted sum), scaled by the scan length; single-device lowerings
    carry none (XLA folds the degenerate collective away). A program
    exceeding this has grown a second synchronization point — the
    exact regression class the one-collective-per-round design
    exists to prevent.

    Under ``client_shards > 1`` the budget is also a FLOOR: the
    sharded seam stages exactly one explicit client-axis all-gather
    per round (``parallel/podscale.py``) which appears ONCE textually
    even inside a scan body, so the auditor certifies the count
    EXACTLY — a sharded program with zero collectives silently
    dropped the cross-shard reduction, which is as much a bug as a
    second sync point. (GSPMD-inserted resharding collectives are
    post-StableHLO and invisible to the textual count.)"""
    if client_shards > 1:
        return 1
    if mesh_devices <= 1:
        return 0
    rounds = num_rounds if dispatch == "scan" else 1
    return rounds


def illegal_reason(source: str, dispatch: str, execution: str, *, cfg,
                   algorithm: FedAlgorithm, model, mesh_devices: int,
                   k_online: int, gather_mode: str = "auto",
                   has_val: bool = False):
    """The reason a cell is unsupported, or None when it is legal.

    ``gather_mode`` is the EXPLICIT (pre-resolution) mode: an
    auto-resolved ``'shard'`` on the resident source is legal; an
    explicitly pinned one on a packed-row program is not."""
    if source not in SOURCES or dispatch not in DISPATCHES \
            or execution not in EXECUTIONS:
        raise ValueError(
            f"unknown round-program cell {cell_name(source, dispatch, execution)}"
            f" — axes are source={SOURCES}, dispatch={DISPATCHES}, "
            f"execution={EXECUTIONS}")

    # -- dispatch axis ---------------------------------------------------
    if dispatch == "scan" and cfg.federated.sync_mode == "async":
        return ("run_rounds scans ONE traced round program over R "
                "rounds' inputs, but async commits are host-scheduled "
                "events (each commit's jobs come from the event "
                "scheduler), so no R-commit program exists to scan — "
                "call run_round once per commit, or use "
                "--sync_mode sync for the scan dispatch")
    if dispatch == "commit":
        alg_name = cfg.effective_algorithm
        if alg_name not in ASYNC_ALGORITHMS:
            return ("sync_mode='async' is unsupported for algorithm "
                    f"{alg_name!r}: it is not wired for stale-snapshot "
                    f"commits (supported: {', '.join(ASYNC_ALGORITHMS)};"
                    " AFL/qFFL aggregate cohort-global losses, DRFA "
                    "adds a dual phase and lambda participation, the "
                    "personalized families need per-client val "
                    "streams, and qsparse's tracking variate assumes "
                    "the round's payload sum)")
        if has_val or algorithm.needs_val_batch or cfg.federated.personal:
            return ("per-client validation splits "
                    "(cfg.federated.personal) are not buffered — "
                    "sync_mode='async' commits carry no val stream")
        if gather_mode == "shard":
            return ("gather_mode='shard' moves whole client shards; "
                    "the commit program packs each buffered job's rows "
                    "(the 'batch' plan) — use gather_mode 'auto' or "
                    "'batch'")

    # -- source axis -----------------------------------------------------
    if source == "feed":
        # full-loss algorithms (qFFL) stream via the 'shard' FEED
        # LAYOUT (whole padded shards packed host-side, rows selected
        # in-program) — resolve_gather_mode picks it; no refusal.
        if not algorithm.participation_replayable:
            return (f"{algorithm.name} samples participation from "
                    "server state the host feed builder cannot see "
                    "(DRFA's lambda-distributed draw) — the schedule "
                    "replay cannot know the cohort before the round")
        if (type(algorithm).post_round_global
                is not FedAlgorithm.post_round_global
                and not algorithm.needs_post_probe):
            return (f"{algorithm.name} overrides post_round_global "
                    "with full-data logic and declares no host probe "
                    "plan (host_probe_fn/post_round_global_feed) the "
                    "feed builder could pack")
        if algorithm.needs_val_batch or has_val:
            return ("per-client validation splits "
                    "(cfg.federated.personal) are not streamed yet")

    # -- client-shard fact (pod-scale cohort sharding) -------------------
    shards = int(getattr(cfg.mesh, "client_shards", 0) or 0)
    if shards > 1:
        if k_online % shards:
            return (f"mesh.client_shards={shards} does not divide the "
                    f"dispatch cohort width k={k_online} — contiguous "
                    "k/shards client blocks are the unit of the "
                    "bitwise hierarchical sum, so the cohort must "
                    "split evenly (adjust online_client_rate or the "
                    "shard count)")
        if cfg.fault.robust_agg != "mean":
            return (f"robust_agg={cfg.fault.robust_agg!r} reduces "
                    "across the FULL cohort axis (median/trim "
                    "selection and norm-bound renormalization are "
                    "cross-client order-sensitive floats) — only the "
                    "hierarchical 'mean' seam is certified bitwise "
                    "under client sharding")
        if cfg.telemetry.cohort_stats:
            return ("telemetry.cohort_stats computes cross-cohort "
                    "dispersion (cosine-to-mean reductions) whose "
                    "float association is not shard-invariant — "
                    "disable cohort_stats under "
                    "mesh.client_shards > 1")
        alg_name = cfg.effective_algorithm
        if alg_name not in ASYNC_ALGORITHMS:
            return (f"algorithm {alg_name!r} is not certified for the "
                    "sharded aggregation seam: only the FedAvg family "
                    f"({', '.join(ASYNC_ALGORITHMS)}) confines its "
                    "cross-client float reductions to the one "
                    "hierarchical weighted sum (AFL/qFFL aggregate "
                    "cohort-global losses, DRFA adds a dual phase, "
                    "and qsparse's tracking variate assumes the "
                    "round's full payload sum)")
        if has_val or algorithm.needs_val_batch \
                or cfg.federated.personal:
            return ("per-client validation splits "
                    "(cfg.federated.personal) reduce across the full "
                    "cohort outside the sharded seam — disable them "
                    "under mesh.client_shards > 1")
        if gather_mode == "shard":
            return ("gather_mode='shard' selects rows in-program via "
                    "the per-step epoch permutation, and that sort's "
                    "cross-device partitioning is not bitwise-stable "
                    "across shard counts — use gather_mode 'auto' or "
                    "'batch' under mesh.client_shards > 1 (auto "
                    "resolves 'batch' on an armed mesh)")
        if dispatch == "commit":
            conc = cfg.federated.async_concurrency or k_online
            m = cfg.federated.async_buffer_size or max(1, conc // 2)
            if m % shards:
                return ("the async commit buffer width m="
                        f"{m} does not divide over "
                        f"mesh.client_shards={shards} — each shard "
                        "must own whole buffered jobs for the commit "
                        "program's hierarchical sum (set "
                        "async_buffer_size to a multiple of the "
                        "shard count)")

    # -- execution axis --------------------------------------------------
    if execution == "sequential":
        why = _sequential_refusal(cfg, algorithm, model, dispatch,
                                  mesh_devices, gather_mode, has_val)
        if why is not None:
            return ("mesh.client_fusion='sequential' runs the cohort "
                    "one client after another into a running weighted "
                    "sum and keeps no per-client copy of the "
                    f"parameters: {why}")

    # -- gather-mode precondition shared by every cell -------------------
    if gather_mode == "batch" and algorithm.needs_full_loss:
        return (f"{algorithm.name} requires gather_mode='shard' "
                "(it evaluates the full local dataset each round)")
    return None


def _sequential_refusal(cfg, algorithm, model, dispatch: str,
                        mesh_devices: int, gather_mode: str,
                        has_val: bool):
    """Why the sequential execution cannot serve this configuration,
    or None. Everything refused here needs the STACKED cohort (a rule
    over all k updates at once, per-client state read back next round)
    or a second program shape this execution does not trace."""
    fed, flt = cfg.federated, cfg.fault
    alg_name = cfg.effective_algorithm
    if alg_name not in SEQUENTIAL_ALGORITHMS:
        return (f"algorithm {alg_name!r} keeps per-client state or a "
                "structured payload that has no fold yet (SCAFFOLD's "
                "and FedGATE's variates, the personalized models, "
                "qFFL's and AFL's cohort-global losses, DRFA's dual "
                f"phase); supported: {', '.join(SEQUENTIAL_ALGORITHMS)}")
    if flt.robust_agg != "mean":
        return (f"robust_agg={flt.robust_agg!r} ranks or trims the k "
                "stacked updates against each other; only the "
                "weighted 'mean' is a fold")
    if flt.chaos_enabled or flt.guard_updates or flt.avail_armed \
            or flt.byzantine_rate > 0.0:
        return ("chaos, update guards, byzantine adversaries and the "
                "availability lifecycle screen and renormalize the "
                "stacked cohort")
    if flt.dp_armed:
        return "the DP stage clips each of the stacked payloads"
    if cfg.telemetry.cohort_stats:
        return "telemetry.cohort_stats reads all k updates at once"
    if fed.quantized or fed.compressed:
        return ("the uplink wire format is applied to the stacked "
                "[k] payloads")
    if dispatch == "commit":
        return ("buffered commits train each job against its own "
                "stale snapshot from the ring")
    if fed.sync_type == "epoch":
        return ("epoch sync freezes clients by a mask over the "
                "lockstep cohort; use --federated_sync_type local_step")
    if cfg.optim.optimizer != "sgd" or (cfg.optim.in_momentum
                                        and cfg.optim.in_momentum_factor):
        return ("a local optimizer with buffers (momentum, Adam) is "
                "per-client parameter-sized state")
    if has_val or algorithm.needs_val_batch or fed.personal:
        return "per-client validation splits are not threaded"
    if gather_mode == "shard" or algorithm.needs_full_loss:
        return "whole-shard gathers are not threaded ('batch' only)"
    if model.is_recurrent:
        return "a recurrent carry is not threaded"
    if mesh_devices > 1 or int(getattr(cfg.mesh, "client_shards", 0)
                               or 0) > 0:
        return (f"the fold is one device's (mesh has {mesh_devices} "
                "devices; client_shards must be 0)")
    return None


def validate_cell(source: str, dispatch: str, execution: str, **facts
                  ) -> None:
    """Raise the cell's ONE named ``ValueError`` when it is illegal.

    This is the single error site for the whole composition matrix —
    trainer construction validates the dispatches it serves
    (round/commit) and ``run_rounds`` validates the scan cell at call
    time, but the message always names the cell the same way."""
    reason = illegal_reason(source, dispatch, execution, **facts)
    if reason is not None:
        raise ValueError(
            "round-program cell "
            f"{cell_name(source, dispatch, execution)} is unsupported "
            f"here: {reason}")


class RoundProgramBuilder:
    """Builds the trainer's jittable programs per (dispatch) request,
    with the source and execution axes read off the trainer (resolved
    at construction). Program signatures by (source, dispatch):

    ======== ========== ==============================================
    source   dispatch   signature
    ======== ========== ==============================================
    resident round      ``fn(server, clients, data, val_data)``
    feed     round      ``fn(server, clients, feed)``
    resident scan-of-R  ``fn(server, clients, data, val_data)``
    feed     scan-of-R  ``fn(server, clients, window)``  (leading [R])
    resident commit     ``fn(server, clients, jobs, data)``
    feed     commit     ``fn(server, clients, jobs, feed)``
    ======== ========== ==============================================

    Each ``build`` call returns a FRESH closure of the same code, so
    the live jits and the uninstrumented cost-capture twins
    (``telemetry/costs.py``) lower byte-identical HLO by construction.
    """

    def __init__(self, trainer):
        self._t = trainer

    @property
    def source(self) -> str:
        return "feed" if self._t.data_plane == "stream" else "resident"

    @property
    def execution(self) -> str:
        return self._t.client_fusion

    def validate(self, dispatch: str) -> None:
        t = self._t
        validate_cell(
            self.source, dispatch, self.execution, cfg=t.cfg,
            algorithm=t.algorithm, model=t.model,
            # over-selection widens the cohort the program actually
            # runs over — validate the dispatch width, not the
            # close-quorum k_online
            mesh_devices=int(t.mesh.devices.size),
            k_online=getattr(t, "k_dispatch", t.k_online),
            gather_mode=t.explicit_gather_mode, has_val=t.has_val)

    def build(self, dispatch: str, *, scan_length: int = 1):
        """Validate the cell, then return its program function."""
        self.validate(dispatch)
        if dispatch == "round":
            return self._t.round_fn if self.source == "resident" \
                else self._t.round_stream_fn
        if dispatch == "scan":
            return self._scan_program(scan_length)
        return self._commit_program()

    # -- scan dispatch ----------------------------------------------------
    def _scan_program(self, num_rounds: int):
        """R rounds under one ``lax.scan``: the host dispatches once
        instead of once per round. On the resident source the scan
        closes over the full data pytree in HBM (the seed fast path);
        on the feed source it consumes an ``[R, k, K*B, ...]`` feed
        WINDOW the producer packed while the device scans the previous
        window — the scanned streamed program that finally gives the
        stream plane the dispatch lever."""
        t = self._t
        if self.source == "resident":
            def rounds_fn(server, clients, data, val_data):
                def body(carry, _):
                    s, c = carry
                    s, c, m = t.round_fn(s, c, data, val_data)
                    return (s, c), m

                (s, c), ms = jax.lax.scan(
                    body, (server, clients), None, length=num_rounds)
                return s, c, ms
        else:
            def rounds_fn(server, clients, window):
                def body(carry, feed):
                    s, c = carry
                    s, c, m = t.round_stream_fn(s, c, feed)
                    return (s, c), m

                (s, c), ms = jax.lax.scan(
                    body, (server, clients), window, length=num_rounds)
                return s, c, ms
        return rounds_fn

    # -- commit dispatch --------------------------------------------------
    def _commit_program(self):
        """The async plane's buffered commit as the one-step member of
        the program family: gather each buffered job's rows (in-program
        on the resident source, from the commit-keyed host feed on the
        feed source), then run ``_round_core`` once through its commit
        seam — per-job snapshot bases from the ring, staleness weights
        composed into the aggregation weights, the ring rotated with
        the new version."""
        t = self._t
        core = self._commit_core
        K, B = t.local_steps, t.batch_size

        def job_rngs(server, jobs):
            # per-job training streams keyed by the GLOBAL dispatch
            # counter, not the commit index — two dispatches of one
            # client against different versions must not share a batch
            # order
            return jax.vmap(lambda d: jax.random.fold_in(
                jax.random.fold_in(server.rng, ASYNC_TRAIN_SALT), d)
            )(jobs.dispatch)

        if self.source == "resident":
            def commit_fn(server, clients, jobs: CommitJobs, data):
                # gather each buffered job's rows in-program (the same
                # round_row_plan the host feed packer replays, so the
                # two commit sources are bitwise-identical)
                rng_round = jax.random.fold_in(server.rng, server.round)
                rngs = job_rngs(server, jobs)
                idx = jobs.idx
                on_sizes = jnp.take(data.sizes, idx)
                rows = jax.vmap(lambda r, s: round_row_plan(
                    r, s, data.x.shape[1], K * B))(rngs, on_sizes)
                on_x, on_y = gather_client_rows(
                    (data.x, data.y), idx, rows)
                pre_x, pre_y = gather_client_rows(
                    (data.x, data.y), idx,
                    jnp.broadcast_to(jnp.arange(B), idx.shape + (B,)))
                return core(server, clients, jobs, on_x, on_y, pre_x,
                            pre_y, on_sizes, rngs, rng_round)
        else:
            def commit_fn(server, clients, jobs: CommitJobs, feed):
                # the commit consumes a host-packed feed built one
                # COMMIT ahead by the producer (keyed by commit
                # version, not round index)
                rng_round = jax.random.fold_in(server.rng, server.round)
                rngs = job_rngs(server, jobs)
                return core(server, clients, jobs, feed.x, feed.y,
                            feed.pre_x, feed.pre_y, feed.sizes, rngs,
                            rng_round)
        return commit_fn

    def _commit_core(self, server, clients, jobs: CommitJobs, on_x,
                     on_y, pre_x, pre_y, on_sizes, rngs, rng_round):
        """Unwrap the snapshot ring, gather each job's snapshot, and
        re-dispatch ``_round_core`` through its commit seam; then
        rotate the ring with the new version."""
        # lazy import: async_plane imports parallel.federated, which
        # imports this module — a module-level import here would close
        # the cycle. Commit programs are only built by the async
        # trainer, by which time async_plane is fully imported.
        from fedtorch_tpu.async_plane.staleness import (
            normalized_staleness_weights,
        )
        from fedtorch_tpu.robustness.chaos import (
            draw_chaos_plan, no_chaos_plan,
        )

        t = self._t
        fed = t.cfg.federated
        alg_aux = server.aux["alg"]
        ring = server.aux["ring"]
        inner = server._replace(aux=alg_aux)
        R = t.snapshot_ring
        slot = jobs.version % R
        take = lambda tr: jax.tree.map(
            lambda x: jnp.take(x, slot, axis=0), tr)
        base_params, base_aux = take(ring["params"]), take(ring["aux"])
        stale = (server.round - jobs.version).astype(jnp.float32)
        weight_scale = normalized_staleness_weights(
            stale, fed.staleness_weight, fed.staleness_exponent)

        # chaos composes: crash/NaN faults draw their usual per-commit
        # folds; the straggler BUDGET cut is neutralized (stragglers
        # already arrived late — cutting their steps too would double-
        # apply the fault)
        m = jobs.idx.shape[0]
        flt = t.fault
        if t.chaos_on:
            plan = draw_chaos_plan(
                jax.random.fold_in(rng_round, flt.chaos_salt), m, flt
            )._replace(budget_scale=jnp.ones((m,)))
        else:
            plan = no_chaos_plan(m)

        # no buffered val plane (a commit-cell gate): same placeholders
        # as the feed source's round program
        on_vx, on_vy = on_x[:, :1], on_y[:, :1]
        on_vsizes = jnp.ones_like(on_sizes)
        new_inner, new_clients, metrics = t._round_core(
            inner, clients, jobs.idx, on_x, on_y, on_vx, on_vy,
            on_sizes, on_vsizes, pre_x, pre_y, rng_round, rngs,
            batch_mode=True, val_batch_mode=False,
            base_params=base_params, base_aux=base_aux,
            weight_scale=weight_scale, plan=plan)

        # rotate the ring: the new commit version overwrites the oldest
        # retained slot (new_inner.round == server.round + 1)
        new_slot = new_inner.round % R
        new_ring = {
            "params": jax.tree.map(
                lambda r, p: r.at[new_slot].set(p),
                ring["params"], new_inner.params),
            "aux": jax.tree.map(
                lambda r, a: r.at[new_slot].set(a),
                ring["aux"], new_inner.aux),
        }
        new_server = new_inner._replace(
            aux={"alg": new_inner.aux, "ring": new_ring})
        metrics = metrics._replace(
            straggler_clients=jnp.sum(jobs.straggler),
            staleness_mean=jnp.mean(stale))
        if metrics.cohort_staleness is not None:
            # cohort stats on: the per-JOB commit staleness replaces
            # _round_core's sync-plane zeros, so the ledger records the
            # staleness each buffered update actually carried
            metrics = metrics._replace(cohort_staleness=stale)
        return new_server, new_clients, metrics


def resolve_gather_mode(gather_mode: str, *, algorithm: FedAlgorithm,
                        data_plane: str, local_steps: int,
                        batch_size: int, n_max: int,
                        client_shards: int = 0) -> str:
    """Resolve the explicit gather mode to 'shard' | 'batch'.

    'batch' gathers only the K*B rows each online client will touch
    this round (bounds cross-device movement when K*B < shard size);
    'shard' moves whole client shards and indexes per step — required
    when the algorithm reads the full local dataset (qFFL's full loss)
    and cheaper when a round revisits the shard (K*B >= n_max). On
    the feed source the mode names the FEED LAYOUT: 'batch' packs the
    round's touched rows host-side (the default — an auto stream
    resolves 'batch' unless the algorithm needs the full loss, since
    the pack already moved exactly the touched rows); 'shard' packs
    whole padded shards and rows are selected in-program, exactly like
    the device shard gather (qFFL's streamed plan). On an armed
    pod-scale mesh (``client_shards >= 1``) auto never picks 'shard'
    by the K*B revisit heuristic: the shard plan's per-step epoch
    permutation is the partitioned-sort hazard ``validate_cell``
    refuses under ``client_shards > 1``, and the armed 1-shard twin
    must resolve identically to its sharded siblings. Refusals
    ('batch' under a full-loss algorithm, explicit 'shard' under
    cohort sharding) are :func:`validate_cell`'s, not this
    function's."""
    if gather_mode not in ("auto", "shard", "batch"):
        raise ValueError(f"unknown gather_mode {gather_mode!r}")
    if data_plane == "stream" and gather_mode == "auto":
        return "shard" if algorithm.needs_full_loss else "batch"
    if gather_mode == "auto":
        return "shard" if (algorithm.needs_full_loss
                           or (client_shards < 1
                               and local_steps * batch_size >= n_max)) \
            else "batch"
    return gather_mode
