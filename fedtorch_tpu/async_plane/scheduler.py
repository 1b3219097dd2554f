"""Deterministic event schedule for the async commit plane.

The asynchronous server is simulated as an in-program discrete-event
system: ``concurrency`` clients are always training ("in flight"), each
against the snapshot version current at its dispatch; per-dispatch
completion delays, straggler flags and mid-round dropouts come from a
pluggable :class:`~fedtorch_tpu.robustness.availability
.AvailabilityModel` — all threefry draws off the experiment key, so
**client completion order is a pure function of (seed, commit)** — the
async plane stays testable, resumable, and trace-once like every other
plane. The default model reproduces the historical draws bitwise: the
chaos subsystem's straggler knobs reinterpreted as wall-clock long
tails (``fault.straggler_rate`` the probability a dispatch lands in
the tail, ``1/fault.straggler_step_frac`` its slowdown). That aliasing
is DEPRECATED spelling (config.finalize warns): ``fault
.avail_model='trace'`` selects the synthetic deployment trace —
device-class speed multipliers + diurnal dropout
(docs/robustness.md "Deployment realism").

One :meth:`AsyncSchedule.next_commit` pops the next ``buffer_size``
arrivals, immediately re-dispatching each arrived client's replacement
(sampled uniformly from the clients neither in flight nor already
buffered) against the current commit version, exactly FedBuff's server
loop (Nguyen et al., arXiv:2106.06639, Alg. 1). No update is ever
materialized before its commit: "in flight" is bookkeeping, and the
jitted commit program computes all m buffered local trainings at once —
which is what makes a preempted async run replayable: a resumed
scheduler fast-forwards the event simulation (cheap, no training FLOPs)
to the checkpoint's commit and the future is bitwise identical.

Like :class:`~fedtorch_tpu.data.streaming.RoundSchedule`, all draws run
jitted on the CPU backend: threefry is backend-deterministic, so the
host replay and the device program cannot diverge.
"""
from __future__ import annotations

import heapq
from typing import List, NamedTuple, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fedtorch_tpu.data.streaming import _cpu_device, _cpu_scope
# the per-dispatch local-training salt lives with the round-program
# family whose PRNG contract it is (parallel/round_program.py);
# re-exported here for the host-replay twins that import it
from fedtorch_tpu.parallel.round_program import ASYNC_TRAIN_SALT  # noqa: F401
from fedtorch_tpu.robustness.availability import (
    LEGACY_DELAY_SALT, AvailabilityModel, DefaultAvailability,
)

# fold constants separating the scheduler's PRNG streams from the
# round streams (chaos_salt 0x7FFFFFFD, the augmentation parent
# 0x7FFFFFFF and ASYNC_TRAIN_SALT 0x7FFFFFF9 are taken; all are
# < 2^31 so fold_in accepts them). The delay salt's source of truth
# moved to robustness/availability.py with the model that owns the
# legacy fold chain; re-exported for the A/B twins that import it.
_DELAY_SALT = LEGACY_DELAY_SALT  # per-dispatch completion delay
_SELECT_SALT = 0x7FFFFFF5        # per-replacement client selection


class HostCommitPlan(NamedTuple):
    """One commit's buffered arrivals, in arrival order (host numpy).

    ``commit`` is the version this commit was built against (== the
    server round that consumes it); committing produces ``commit+1``."""
    commit: int
    idx: np.ndarray        # [m] int32 client ids (distinct)
    version: np.ndarray    # [m] int32 snapshot version each trained on
                           # (clamped into the ring window)
    dispatch: np.ndarray   # [m] int32 global dispatch counter (rng fold)
    straggler: np.ndarray  # [m] float32 {0,1} — tail-delay dispatches
    arrival_times: np.ndarray  # [m] float64 virtual arrival times
    commit_time: float     # virtual time the buffer filled


class ScheduleStats(NamedTuple):
    dispatches: int
    stragglers: int
    staleness_clamped: int  # arrivals older than the snapshot ring
    dropouts: int = 0       # mid-round dropouts (arrival discarded,
                            # replacement dispatched)


class AsyncSchedule:
    """The event simulation. Pure function of (key, constructor args);
    two instances with equal arguments produce identical commit
    sequences (the stream-plane producer and the trainer each hold
    one), and ``start_commit > 0`` fast-forwards a fresh instance to a
    resumed run's commit."""

    def __init__(self, key_data, key_impl, *, num_clients: int,
                 concurrency: int, buffer_size: int, ring_size: int,
                 straggler_rate: float, straggler_step_frac: float,
                 jitter: float = 0.25, start_commit: int = 0,
                 model: AvailabilityModel = None,
                 participation_mode: str = "perm"):
        if buffer_size < 1 or concurrency < 1:
            raise ValueError("buffer_size and concurrency must be >= 1")
        if participation_mode not in ("perm", "sparse"):
            raise ValueError(
                f"participation_mode must be 'perm' or 'sparse', got "
                f"{participation_mode!r}")
        if num_clients < concurrency + buffer_size:
            raise ValueError(
                f"async plane needs num_clients >= concurrency + "
                f"buffer_size ({concurrency} + {buffer_size}) so every "
                f"arrival has a distinct replacement to dispatch; got "
                f"{num_clients} clients")
        self.num_clients = num_clients
        self.concurrency = concurrency
        self.buffer_size = buffer_size
        self.ring_size = ring_size
        # 'perm' draws a [C] uniform score vector per selection (the
        # legacy bitwise-pinned stream); 'sparse' draws SCALAR uniform
        # ids with rejection — O(1) memory per draw, the
        # million-client mode (config.PARTICIPATION_MODES)
        self.participation_mode = participation_mode
        self._rate = float(straggler_rate)
        self._tail = 1.0 / float(straggler_step_frac)
        self._jitter = float(jitter)
        # no model = the pre-availability scheduler, bitwise: the
        # default model owns the exact legacy fold chain
        self._model = model if model is not None else \
            DefaultAvailability(straggler_rate=straggler_rate,
                                straggler_step_frac=straggler_step_frac,
                                jitter=jitter)

        self._cpu = _cpu_device()
        with self._scope():
            self._key = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(key_data)), impl=key_impl)

            delays = self._model.traced

            if participation_mode == "sparse":
                def select(key, select_id):
                    r = jax.random.fold_in(
                        jax.random.fold_in(key, _SELECT_SALT),
                        select_id)
                    return jax.random.randint(r, (), 0, num_clients,
                                              dtype=jnp.int32)
            else:
                def select(key, select_id):
                    r = jax.random.fold_in(
                        jax.random.fold_in(key, _SELECT_SALT),
                        select_id)
                    return jax.random.uniform(r, (num_clients,))

            # the key input is reused by every draw — donation would
            # invalidate it; outputs are a few bytes
            # lint: disable=FTL004 — key reused by every event draw
            self._delays_jit = jax.jit(delays)
            # lint: disable=FTL004 — key reused by every event draw
            self._select_jit = jax.jit(select)

        # event state: min-heap of (finish_time, dispatch_id, client,
        # version, straggler, dropped) — dispatch_id breaks
        # (measure-zero) ties deterministically
        self._heap: List[Tuple[float, int, int, int, bool, bool]] = []
        self._inflight: Set[int] = set()
        self._dispatch_count = 0
        self._select_count = 0
        self._commit = 0
        self._stragglers = 0
        self._dropouts = 0
        self._clamped = 0
        self.commit_times: List[float] = []
        # staleness histogram: {commits-stale: count} over every
        # buffered update committed so far (post ring-clamp — the
        # staleness the aggregation actually damped). Host-only
        # telemetry (docs/observability.md); a fast-forwarded resume
        # rebuilds it exactly, since the sim replays every commit.
        self.staleness_hist: dict = {}

        # initial cohort: ``concurrency`` distinct clients against
        # version 0 at time 0
        if participation_mode == "sparse":
            cohort: List[int] = []
            taken: Set[int] = set()
            while len(cohort) < concurrency:
                c = self._select_id()
                if c not in taken:
                    taken.add(c)
                    cohort.append(c)
            for c in cohort:
                self._dispatch(c, version=0, now=0.0)
        else:
            scores = self._select_scores()
            for c in np.argsort(scores, kind="stable")[:concurrency]:
                self._dispatch(int(c), version=0, now=0.0)
        for _ in range(start_commit):
            self.next_commit()

    def _scope(self):
        return _cpu_scope(self._cpu)

    def _select_scores(self) -> np.ndarray:
        with self._scope():
            s = self._select_jit(self._key, np.int32(self._select_count))
            self._select_count += 1
            return np.asarray(jax.device_get(s))

    def _select_id(self) -> int:
        """One SCALAR uniform client draw ('sparse' mode) — same
        (salt, count) fold chain as the perm scores, but O(1) memory;
        the count advances per DRAW, so rejections consume entropy
        deterministically."""
        with self._scope():
            c = self._select_jit(self._key, np.int32(self._select_count))
            self._select_count += 1
            return int(jax.device_get(c))

    def _draw_delays(self, dispatch_ids: np.ndarray,
                     clients: np.ndarray, versions: np.ndarray):
        """One jitted model draw per dispatch batch -> float64 host
        math in the model's ``finish`` (the default model's split is
        bitwise-identical to the historical inline computation)."""
        versions = np.asarray(versions, np.int32)
        with self._scope():
            u = jax.device_get(self._delays_jit(
                self._key, np.asarray(dispatch_ids, np.int32),
                np.asarray(clients, np.int32), versions))
        return self._model.finish(np.asarray(u, np.float64), versions)

    def _dispatch(self, client: int, version: int, now: float) -> None:
        did = self._dispatch_count
        self._dispatch_count += 1
        delay, straggler, dropped = self._draw_delays(
            np.asarray([did]), np.asarray([client]),
            np.asarray([version]))
        if straggler[0]:
            self._stragglers += 1
        heapq.heappush(self._heap, (now + float(delay[0]), did, client,
                                    version, bool(straggler[0]),
                                    bool(dropped[0])))
        self._inflight.add(client)

    def _pick_replacement(self, exclude: Set[int]) -> int:
        if self.participation_mode == "sparse":
            # rejection sampling: |exclude| <= concurrency +
            # buffer_size - 1 < num_clients (constructor guard), so
            # acceptance probability is > 0 and at million-client
            # scale is ~1 — expected O(1) scalar draws, never a [C]
            # score vector
            while True:
                c = self._select_id()
                if c not in exclude:
                    return c
        scores = self._select_scores()
        for c in np.argsort(scores, kind="stable"):
            if int(c) not in exclude:
                return int(c)
        raise RuntimeError("no dispatchable client (guarded by the "
                           "num_clients >= concurrency + buffer check)")

    def next_commit(self) -> HostCommitPlan:
        """Pop the next ``buffer_size`` arrivals; re-dispatch each
        arrival's replacement immediately (against the CURRENT commit
        version — the buffer is not yet full, so no new version exists
        for it to see)."""
        m = self.buffer_size
        buffer: List[Tuple[float, int, int, int, bool]] = []
        buffered: Set[int] = set()
        while len(buffer) < m:
            t, did, client, version, straggler, dropped = \
                heapq.heappop(self._heap)
            self._inflight.discard(client)
            if dropped:
                # mid-round dropout: the arrival never reports — the
                # update is discarded (it was never materialized; "in
                # flight" is bookkeeping) and the slot re-fills. The
                # dropped client is offline, so it is excluded from
                # its own replacement draw.
                self._dropouts += 1
                repl = self._pick_replacement(
                    self._inflight | buffered | {client})
                self._dispatch(repl, version=self._commit, now=t)
                continue
            buffer.append((t, did, client, version, straggler))
            buffered.add(client)
            repl = self._pick_replacement(self._inflight | buffered)
            self._dispatch(repl, version=self._commit, now=t)

        floor = max(self._commit - (self.ring_size - 1), 0)
        versions = np.asarray([v for _, _, _, v, _ in buffer], np.int64)
        clamped = np.maximum(versions, floor)
        self._clamped += int(np.sum(clamped != versions))
        for s in (self._commit - clamped).tolist():
            self.staleness_hist[int(s)] = \
                self.staleness_hist.get(int(s), 0) + 1
        plan = HostCommitPlan(
            commit=self._commit,
            idx=np.asarray([c for _, _, c, _, _ in buffer], np.int32),
            version=clamped.astype(np.int32),
            dispatch=np.asarray([d for _, d, _, _, _ in buffer],
                                np.int32),
            straggler=np.asarray([s for *_, s in buffer], np.float32),
            arrival_times=np.asarray([t for t, *_ in buffer]),
            commit_time=buffer[-1][0])
        self._commit += 1
        self.commit_times.append(plan.commit_time)
        return plan

    @property
    def commit(self) -> int:
        return self._commit

    @property
    def stats(self) -> ScheduleStats:
        return ScheduleStats(dispatches=self._dispatch_count,
                             stragglers=self._stragglers,
                             staleness_clamped=self._clamped,
                             dropouts=self._dropouts)


def simulate_sync_round_times(key_data, key_impl, *, rounds: int,
                              k_online: int, straggler_rate: float,
                              straggler_step_frac: float,
                              jitter: float = 0.25) -> np.ndarray:
    """Virtual duration of each SYNC round under the same delay model:
    the server blocks on all k online clients, so a round costs the MAX
    of its k dispatch delays — the straggler sets the round clock.
    Comparable with :attr:`AsyncSchedule.commit_times`."""
    with _cpu_scope(_cpu_device()):
        key = jax.random.wrap_key_data(
            jnp.asarray(np.asarray(key_data)), impl=key_impl)
        ids = jnp.arange(rounds * k_online, dtype=jnp.int32)
        rngs = jax.vmap(lambda d: jax.random.fold_in(
            jax.random.fold_in(key, _DELAY_SALT), d))(ids)
        u = np.asarray(jax.device_get(jax.vmap(
            lambda r: jax.random.uniform(r, (2,)))(rngs)), np.float64)
    base = 1.0 + jitter * u[:, 1]
    tail = 1.0 / float(straggler_step_frac)
    delays = np.where(u[:, 0] < straggler_rate, base * tail, base)
    return delays.reshape(rounds, k_online).max(axis=1)
