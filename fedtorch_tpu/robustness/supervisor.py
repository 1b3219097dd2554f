"""Host-side round supervisor: rollback + retry instead of dying.

The reference's failure story is fail-stop: a diverged model or a dead
process kills the whole ``mpirun`` job and the operator restarts from
whatever checkpoint exists. The supervisor wraps
``FederatedTrainer.run_round`` with production semantics:

1. snapshot the round state (device-level copies — the round jit
   DONATES its inputs, so the snapshot must own its buffers);
2. run the round and health-check the result: non-finite server params
   always count as divergence; with ``fault.loss_blowup_factor > 0`` a
   mean online loss above that multiple of the running loss EMA does
   too;
3. on divergence, roll back to the snapshot and retry with exponential
   backoff. Each retry folds the attempt number into the server PRNG
   (``fault.reseed_on_retry``) — a deterministic program replayed
   unchanged would reproduce the failure, so the retry draws a fresh
   participation/chaos schedule;
4. after ``fault.max_retries`` failed retries, degrade gracefully: keep
   the rolled-back (healthy) state, advance the round counter (the
   round is SKIPPED, not silently re-run forever), and invoke the
   ``on_round_skipped(round_idx, cause)`` and ``on_degrade`` hooks —
   the place to e.g. scale the learning rate down or alert an
   operator.

Skips carry a CAUSE: ``"fault"`` (divergence or a raising round
program exhausted its retries) vs ``"quorum"`` (the deployment-realism
lifecycle reported a sub-quorum cohort and
``fault.avail_quorum_action='abort'`` escalates it here instead of
committing the degraded partial aggregate — see
robustness/availability.py and docs/robustness.md "Deployment
realism"). A quorum abort retries exactly like divergence — the retry
reseed draws a fresh participation/availability schedule, which is the
whole point of aborting — and only skips when every attempt stayed
below quorum.

If the in-memory snapshot is itself sick (the caller handed in diverged
state), the supervisor falls back to the last on-disk checkpoint when a
``checkpoint_dir`` is configured (utils/checkpoint.py skips corrupt or
truncated files instead of raising).

Exceptions from the round program (XLA runtime errors) are retried the
same way; if EVERY attempt raised — nothing ever produced state to
health-check — the last exception is re-raised, because skipping a
round cannot fix a structurally broken program.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from fedtorch_tpu import telemetry
from fedtorch_tpu.config import FaultConfig
from fedtorch_tpu.core.state import RoundMetrics
from fedtorch_tpu.robustness.guards import all_rejected_scalars
from fedtorch_tpu.utils.diagnostics import model_norms


def tree_device_copy(tree):
    """Owning device copies of every leaf — safe to hold across a jit
    call that donates the originals. Typed PRNG keys can't go through
    ``jnp.copy``; round-trip their raw key data instead."""
    def cp(x):
        dt = getattr(x, "dtype", None)
        if dt is not None and jnp.issubdtype(dt, jax.dtypes.prng_key):
            return jax.random.wrap_key_data(
                jnp.copy(jax.random.key_data(x)),
                impl=jax.random.key_impl(x))
        return jnp.copy(x)
    return jax.tree.map(cp, tree)


@dataclasses.dataclass
class SupervisorStats:
    """Host-side counters; read them after (or during) training."""
    rounds: int = 0
    healthy_rounds: int = 0
    retries: int = 0
    rollbacks: int = 0
    skipped_rounds: int = 0
    # skipped_rounds split by cause (skipped_rounds stays the total):
    # "fault" = divergence / raising program; "quorum" = sub-quorum
    # cohort under avail_quorum_action='abort'
    skipped_fault: int = 0
    skipped_quorum: int = 0
    disk_restores: int = 0
    # rounds where the guards rejected EVERY surviving update (renorm
    # scale 0 — the server held; see guards.all_rejected_scalars)
    all_rejected_rounds: int = 0
    # host-plane seam failures that escaped their own recovery layer
    # and reached the supervisor, keyed by seam name (host_recovery
    # HostSeamError carries the seam) — a repeatedly-failing seam is
    # an operator signal even when every round eventually retries
    # through
    host_seam_failures: dict = dataclasses.field(default_factory=dict)
    last_good_round: int = -1
    loss_ema: Optional[float] = None


class RoundSupervisor:
    """Fault-tolerant wrapper around ``trainer.run_round``.

    Drop-in: ``run_round(server, clients) -> (server, clients, metrics)``
    with the same donation-friendly contract (the caller's buffers may
    be consumed). ``on_degrade(server, clients, stats)`` may return a
    replacement ``(server, clients)`` pair or None to keep the
    rolled-back state. ``sleep_fn`` is injectable for tests."""

    # healthy-loss EMA smoothing for the blow-up detector
    EMA_ALPHA = 0.1
    # PRNG fold base for retry reseeding; far outside the round-index
    # folds the engine uses on this key
    RESEED_SALT = 0x5EED0000

    def __init__(self, trainer, fault: Optional[FaultConfig] = None,
                 checkpoint_dir: Optional[str] = None,
                 on_degrade: Optional[Callable] = None,
                 on_all_rejected: Optional[Callable] = None,
                 on_host_fault: Optional[Callable] = None,
                 on_round_skipped: Optional[Callable] = None,
                 logger=None, sleep_fn: Callable[[float], None] = time.sleep):
        self.trainer = trainer
        self.fault = fault if fault is not None else trainer.cfg.fault
        self.checkpoint_dir = checkpoint_dir
        self.on_degrade = on_degrade
        # operator hook for all-rejected rounds (guards rejected every
        # update — renorm scale 0, the server held). Called as
        # on_all_rejected(round_idx, scalars) AFTER the round is
        # otherwise accepted as healthy: a held round is not
        # divergence, but an operator blind spot if nothing surfaces it
        self.on_all_rejected = on_all_rejected
        # operator hook for repeated host-plane seam failures: called
        # as on_host_fault(seam, total_count, exc) whenever a round
        # attempt raises a seam-named HostSeamError (a host path that
        # exhausted its OWN retry/rebuild budget); total_count is the
        # seam's CUMULATIVE failure count this run (the same value
        # accumulated in stats.host_seam_failures). The supervisor
        # still rolls back and retries the round; the hook is where an
        # operator escalates — e.g. switch data_plane, page someone —
        # when one seam keeps failing
        self.on_host_fault = on_host_fault
        # operator hook for every skipped round, called as
        # on_round_skipped(round_idx, cause) with cause in
        # {"fault", "quorum"} BEFORE on_degrade — the cause split is
        # the operator signal (a run skipping on quorum wants more
        # over-selection or a lower quorum, not a numerics bisect)
        self.on_round_skipped = on_round_skipped
        self.logger = logger
        self.sleep_fn = sleep_fn
        self.stats = SupervisorStats()
        # host scalars of the round that just passed the health check,
        # for the driver loop to log without a second device fetch;
        # None after a skipped round (there is nothing real to log)
        self.last_scalars = None

    # -- health ---------------------------------------------------------
    def _round_health(self, server, clients, metrics: RoundMetrics) \
            -> dict:
        """ONE batched device->host fetch of everything the per-round
        health checks read — the trainer's full log-scalar dict plus
        the finite flag and round index — instead of a blocking
        transfer per scalar (lint FTL001). The fetched scalars are
        kept on ``self.last_scalars`` so the host round loop reuses
        them instead of paying a second transfer."""
        h, (finite, rnd) = self.trainer.round_host_fetch(
            clients, metrics,
            (model_norms(server.params)["all_finite"], server.round))
        h["finite"], h["round"] = float(finite), float(rnd)
        self.last_scalars = h
        n = h["n_online"]
        return {"finite": bool(h["finite"]), "n": n,
                "loss": h["loss_sum"] / max(n, 1.0),
                "round": int(h["round"])}

    def _healthy(self, health: dict) -> bool:
        if not health["finite"]:
            return False
        f = self.fault.loss_blowup_factor
        if f > 0.0 and health["n"] > 0:
            loss = health["loss"]
            if not math.isfinite(loss):
                return False
            ema = self.stats.loss_ema
            if ema is not None and loss > f * ema:
                return False
        return True

    def _quorum_abort(self) -> bool:
        """True when the round just health-checked reported a
        sub-quorum cohort AND the config escalates that here instead
        of committing the degraded partial aggregate. Reads the
        ``quorum_degraded`` flag off the same batched fetch
        ``_round_health`` already paid for (getattr: fakes/mocks in
        tests may carry a bare fault object)."""
        flt = self.fault
        if getattr(flt, "avail_quorum_action", "degrade") != "abort" \
                or getattr(flt, "avail_quorum_frac", 0.0) <= 0.0:
            return False
        s = self.last_scalars or {}
        return s.get("quorum_degraded", 0.0) > 0.0

    def _note_healthy(self, health: dict) -> None:
        st = self.stats
        st.healthy_rounds += 1
        st.last_good_round = health["round"] - 1
        loss = health["loss"]
        # a zero-participation round (all online clients crashed)
        # carries no loss observation: feeding its 0.0 into the EMA
        # would decay it toward 0 and wedge the blow-up check into
        # rejecting every genuine round afterwards
        if health["n"] > 0 and math.isfinite(loss):
            st.loss_ema = loss if st.loss_ema is None else (
                (1 - self.EMA_ALPHA) * st.loss_ema + self.EMA_ALPHA * loss)

    def _log(self, msg: str) -> None:
        if self.logger is not None:
            self.logger.log(msg)

    # -- rollback sources ----------------------------------------------
    def _restore(self, snapshot):
        """Fresh copies of the snapshot (each retry's jit call donates
        what it is handed, so the snapshot itself must never be passed
        in). Falls back to the on-disk checkpoint if the snapshot is
        sick — only possible when the caller handed in diverged state."""
        server, clients = snapshot
        if bool(model_norms(server.params)["all_finite"]):
            return tree_device_copy(server), tree_device_copy(clients)
        if self.checkpoint_dir is not None:
            from fedtorch_tpu.utils.checkpoint import maybe_resume
            try:
                s, c, _, resumed = maybe_resume(
                    self.checkpoint_dir, tree_device_copy(server),
                    tree_device_copy(clients), self.trainer.cfg)
            except FileNotFoundError:
                resumed = False
            if resumed:
                self.stats.disk_restores += 1
                self._log("supervisor: in-memory snapshot non-finite; "
                          "restored last on-disk checkpoint "
                          f"(round {int(s.round)})")
                return s, c
        # nothing better exists; hand back the snapshot as-is
        return tree_device_copy(server), tree_device_copy(clients)

    def _skip_metrics(self) -> RoundMetrics:
        # per-client metrics match round_fn's RoundMetrics shapes
        # (stacking per-round histories must work across healthy and
        # skipped rounds): the trainer says whether that is the full
        # [C] or the sparse mode's cohort-aligned [k]
        z = jnp.zeros((self.trainer.metrics_width,))
        s = jnp.zeros(())
        return RoundMetrics(train_loss=z, train_acc=z, online_mask=z,
                            comm_bytes=s, dropped_clients=s,
                            straggler_clients=s, rejected_updates=s,
                            clipped_updates=s)

    # -- the supervised round -------------------------------------------
    def run_round(self, server, clients):
        flt = self.fault
        self.stats.rounds += 1
        snapshot = (tree_device_copy(server), tree_device_copy(clients))
        round_idx = int(jax.device_get(server.round))
        last_exc: Optional[Exception] = None
        produced_state = False
        cause = "fault"

        for attempt in range(flt.max_retries + 1):
            try:
                out_s, out_c, metrics = self.trainer.run_round(
                    server, clients)
                jax.block_until_ready(out_s.params)
                produced_state = True
                health = self._round_health(out_s, out_c, metrics)
                healthy = self._healthy(health)
                if healthy and self._quorum_abort():
                    # numerically healthy but sub-quorum under the
                    # 'abort' action: roll back and retry like a
                    # divergence — the reseed draws a fresh
                    # availability schedule
                    cause = "quorum"
                    self.last_scalars = None
                    why = ("reporting cohort below quorum "
                           "(avail_quorum_action='abort')")
                elif healthy:
                    self._note_healthy(health)
                    if (self.fault.guard_updates
                            or self.fault.chaos_enabled) \
                            and all_rejected_scalars(self.last_scalars):
                        self.stats.all_rejected_rounds += 1
                        telemetry.event("guards.all_rejected",
                                        round=health["round"] - 1,
                                        n_online=self.last_scalars[
                                            "n_online"],
                                        rejected=self.last_scalars[
                                            "rejected"],
                                        dropped=self.last_scalars[
                                            "dropped"])
                        self._log(
                            f"supervisor: round {health['round'] - 1} "
                            "rejected every update — server held "
                            "(renorm scale 0)")
                        if self.on_all_rejected is not None:
                            self.on_all_rejected(health["round"] - 1,
                                                 self.last_scalars)
                    return out_s, out_c, metrics
                else:
                    cause = "fault"
                    self.last_scalars = None  # unhealthy: don't log
                    why = "non-finite server params or loss blow-up"
            except Exception as e:  # XLA runtime / dispatch failures
                last_exc = e
                cause = "fault"
                why = f"round program raised: {e!r}"
                seam = getattr(e, "seam", None)
                if seam is not None:
                    # a host seam failed past its own recovery budget
                    # (host_recovery.HostSeamError names it): count it
                    # per seam and give the operator hook a chance to
                    # escalate before the generic retry below
                    n = self.stats.host_seam_failures.get(seam, 0) + 1
                    self.stats.host_seam_failures[seam] = n
                    telemetry.event("supervisor.host_fault",
                                    round=round_idx, seam=seam,
                                    failures=n)
                    if self.on_host_fault is not None:
                        self.on_host_fault(seam, n, e)

            self.stats.rollbacks += 1
            telemetry.event("supervisor.rollback", round=round_idx,
                            attempt=attempt + 1, why=why)
            server, clients = self._restore(snapshot)
            # the streaming data plane replays (rng, round) host-side;
            # a rollback (and the reseed below) rewrites both out from
            # under its prefetched feeds — drop them so the retry
            # re-syncs from the restored state (getattr: fakes/mocks
            # in tests need not implement the streaming surface)
            getattr(self.trainer, "invalidate_stream", lambda: None)()
            self._log(f"supervisor: round {round_idx} attempt "
                      f"{attempt + 1}/{flt.max_retries + 1} diverged "
                      f"({why}); rolled back")
            if attempt < flt.max_retries:
                self.stats.retries += 1
                self.sleep_fn(flt.backoff_base_s * (2.0 ** attempt))
                if flt.reseed_on_retry:
                    server = server._replace(rng=jax.random.fold_in(
                        server.rng, self.RESEED_SALT + attempt + 1))

        if not produced_state and last_exc is not None:
            # every attempt raised — a broken program, not divergence
            raise last_exc

        # degrade: keep the healthy rolled-back state, skip the round
        self.stats.skipped_rounds += 1
        if cause == "quorum":
            self.stats.skipped_quorum += 1
        else:
            self.stats.skipped_fault += 1
        telemetry.event("supervisor.round_skipped", round=round_idx,
                        attempts=flt.max_retries + 1, cause=cause)
        server = server._replace(round=server.round + 1)
        self._log(f"supervisor: round {round_idx} skipped after "
                  f"{flt.max_retries + 1} attempts (cause={cause}); "
                  "state rolled back")
        if self.on_round_skipped is not None:
            self.on_round_skipped(round_idx, cause)
        if self.on_degrade is not None:
            replaced = self.on_degrade(server, clients, self.stats)
            if replaced is not None:
                server, clients = replaced
        return server, clients, self._skip_metrics()
