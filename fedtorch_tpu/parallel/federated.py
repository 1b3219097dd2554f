"""The federated round engine — one jitted XLA program per round.

This replaces the reference's entire MPI round machinery
(comms/trainings/federated/main.py:34-213): client sampling, server-model
distribution, the local-SGD hot loop, per-algorithm corrections, and the
gather/sum/broadcast aggregation — as a single pure function

    round_fn(server, clients, data) -> (server', clients', metrics)

compiled once and executed per communication round.

Design (SURVEY.md §7):
* Clients are a leading [C] pytree axis sharded over the mesh; ``vmap``
  over that axis is the reference's centered mode, the sharded execution
  is its MPI mode — one code path for both.
* Partial participation: a static ``k = int(rate*C)`` clients are gathered
  by index each round (the reference's per-round ``new_group`` of online
  clients, main.py:61-65), so offline clients cost zero FLOPs. Round 0
  forces client 0 online (main.py:62-63).
* The local loop is a fixed-length ``lax.scan`` (K steps), sized for the
  LARGEST client (nodes_centered.py:47-50 epochs -> steps). Epoch-sync
  mode reproduces the reference's per-client early loop exit
  (flow_utils.py:33-40 ``is_sync_fed``) by masking: a client whose own
  epoch budget ``ceil(size/B)*E`` is exhausted keeps executing scan steps
  in lockstep but its params/opt/aux/counters freeze and its metrics stop
  accumulating — so under heavy size skew every client takes exactly the
  reference's number of effective steps.
* Aggregation: payloads are weighted client-side (fedavg.py:18-34
  delta-as-grad with rank weights) and tree-summed over the client axis —
  a ``psum``-shaped reduction XLA lowers onto ICI. Every device applies
  the same server step (replicated-server semantics, fedavg.py:89-97).
* Program composition (parallel/round_program.py — the round-program
  builder): data source (resident HBM store with in-program gathers |
  host-packed feed built ahead by ``data/streaming.py``) x dispatch
  (per-round | ``lax.scan``-of-R, incl. the scanned streamed program
  over an [R, ...] feed window | async one-step commit) x client
  execution (vmap | sequential) compose orthogonally; illegal cells are
  refused by ONE named ValueError from ``validate_cell``. Every cell
  funnels into ``_round_core`` and shares ``round_row_plan``, so
  trajectories are bitwise-identical across sources and dispatches
  (docs/performance.md "The round-program builder").
* Fault tolerance (docs/robustness.md): ``cfg.fault`` drives a
  deterministic in-program chaos layer (client crashes masked out of
  aggregation with weight renormalization, straggler step cuts on the
  epoch-sync freeze mask, NaN-poisoned uploads, byzantine adversaries
  crafting finite wire uploads) and server-side update guards
  (non-finite / norm-exploded deltas rejected or clipped before the
  sum). ``cfg.fault.robust_agg`` swaps the aggregation seam for a
  byzantine-robust rule (coordinate median, trimmed mean,
  krum/multikrum selection, centered norm-bounding —
  robustness/aggregators.py) shared by the sync round and the async
  commit. All gating is static config — faults off traces the exact
  fault-free program and ``robust_agg='mean'`` the exact pre-robust
  aggregation.
"""
from __future__ import annotations

import math
import weakref
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from fedtorch_tpu.algorithms.base import (FedAlgorithm, num_online_effective)
from fedtorch_tpu.config import ExperimentConfig
from fedtorch_tpu.core import optim
from fedtorch_tpu.core.losses import make_criterion, per_sample_loss
from fedtorch_tpu.core.schedule import LRSchedule, compile_schedule, lr_at
from fedtorch_tpu.core.state import (
    ClientState, RoundMetrics, ServerState, tree_bytes, tree_sub,
    tree_where, tree_zeros_like,
)
from fedtorch_tpu.data.batching import (
    VAL_FOLD, ClientData, epoch_permutation, gather_client_rows,
    pad_client_axis, round_row_plan, take_batch,
)
from fedtorch_tpu.data.streaming import (
    HostClientStore, MmapClientStore, RoundFeed, StreamFeedProducer,
)
from fedtorch_tpu.models.common import ModelDef, is_token_model
from fedtorch_tpu.ops.augment import augment_image_batch
from fedtorch_tpu.parallel.round_program import (
    RoundProgramBuilder, resolve_gather_mode,
)
from fedtorch_tpu.parallel.mesh import (
    client_sharding, cohort_sharding, local_cohort_rows, make_mesh,
    mesh_client_shards, padded_client_count, replicate,
    replicated_sharding, shard_clients,
)
from fedtorch_tpu.parallel.podscale import (
    cohort_allreduce_bytes, cohort_hierarchical_sum,
)
from fedtorch_tpu import telemetry
from fedtorch_tpu.robustness import host_recovery
from fedtorch_tpu.robustness.aggregators import (
    cohort_statistics, robust_aggregate,
)
from fedtorch_tpu.robustness.chaos import (
    BYZ_COHORT_FOLD, BYZ_NOISE_FOLD, apply_byzantine,
    byzantine_cohort_mask, draw_chaos_plan, no_chaos_plan, poison_tree,
)
from fedtorch_tpu.robustness.availability import sync_lifecycle
from fedtorch_tpu.robustness.guards import (
    renormalize_accepted, screen_payloads,
)
from fedtorch_tpu.robustness.privacy import (
    dp_add_noise, dp_clip_payloads, dp_noise_stddev,
)
from fedtorch_tpu.utils.tracing import instrument_trace

# RoundMetrics' scalar leaves that ride the round's one fetch
# (FederatedTrainer.round_host_fetch), as (the key the host loop logs
# it under, the field). A field whose leaf is None has no key: absent,
# not 0. Keys and the program's values both come from this one table.
_SCALAR_FIELDS = (
    ("comm_bytes", "comm_bytes"),
    ("dropped", "dropped_clients"),
    ("stragglers", "straggler_clients"),
    ("rejected", "rejected_updates"),
    ("clipped", "clipped_updates"),
    # async commit plane: mean snapshot staleness this commit consumed
    # (0.0 on the sync planes)
    ("staleness", "staleness_mean"),
    # byzantine adversary + robust aggregation counters (0 when off)
    ("byzantine", "byzantine_clients"),
    ("robust_selected", "robust_selected"),
    ("robust_trimmed", "robust_trimmed"),
    # deployment-realism lifecycle counters (0 when the availability
    # plane is disarmed); the supervisor reads quorum_degraded from
    # here for the avail_quorum_action='abort' escalation
    ("avail_dropped", "avail_dropped"),
    ("deadline_missed", "deadline_missed"),
    ("quorum_degraded", "quorum_degraded"),
    # the heterogeneity gauge (telemetry.cohort_stats); None when off
    ("cohort_dispersion", "cohort_dispersion"),
    # privacy-plane gauges (fault.dp_noise_multiplier > 0): clip
    # saturation + applied noise stddev; None when DP is off
    ("dp_clipped_frac", "dp_clipped_frac"),
    ("dp_noise_sigma", "dp_noise_sigma"),
)
# what the program computes itself, ahead of the table's leaves
_COMPUTED_SCALARS = ("mean_epoch", "lr", "n_online", "loss_sum",
                     "acc_sum")


# the per-client cohort vectors (cohort_fetch_dev's, for the ledger):
# the scalar program reads none of them, so it is not handed them
_NO_COHORT_VECTORS = dict.fromkeys(
    ("cohort_idx", "cohort_online", "cohort_accept", "cohort_selected",
     "cohort_suspicion", "cohort_staleness", "cohort_norm_q"))


def _scalar_leaves(metrics: RoundMetrics, gauge_names) -> dict:
    """key -> leaf of the table's fields that are present, in the
    table's order, then the model's own gauges under ``gauge_names``
    (``metrics.model_gauges``, where the round made them)."""
    leaves = ((key, getattr(metrics, field))
              for key, field in _SCALAR_FIELDS)
    out = {key: leaf for key, leaf in leaves if leaf is not None}
    out.update(zip(gauge_names, metrics.model_gauges or ()))
    return out


def _sparse_participation(rng: jax.Array, num_clients: int,
                          k: int) -> jnp.ndarray:
    """Uniform without-replacement draw of k ids from [0, C) with O(k)
    MEMORY — no [C] permutation is ever materialized (the
    'sparse' participation mode; docs/performance.md "The
    million-client store"). Sparse Fisher-Yates: draw i picks a rank
    ``j ~ U[0, C-i)`` among the still-unselected ids and maps it to a
    client id by walking the already-selected set in ascending order
    (``v += (v >= s)`` per selected s) — O(k^2 log k) work total,
    which for per-round cohorts is noise next to the round itself.
    Same law as ``permutation(rng, C)[:k]``, different stream (the
    legacy 'perm' mode stays the bitwise-pinned default)."""
    sentinel = jnp.int32(num_clients)

    def draw(sel, i):
        j = jax.random.randint(jax.random.fold_in(rng, i), (), 0,
                               num_clients - i, dtype=jnp.int32)
        # unfilled slots hold the sentinel C: v < C always, so they
        # never shift v — the walk only sees real selections
        v, _ = jax.lax.scan(
            lambda a, s: (a + (a >= s).astype(jnp.int32), None),
            j, jnp.sort(sel))
        return sel.at[i].set(v), v

    _, idx = jax.lax.scan(draw, jnp.full((k,), sentinel, jnp.int32),
                          jnp.arange(k))
    return idx


def participation_indices(rng: jax.Array, num_clients: int, k: int,
                          round_idx: jnp.ndarray,
                          mode: str = "perm") -> jnp.ndarray:
    """k online clients, uniformly without replacement
    (misc.py:10-19 permutation sampling); round 0 forces client 0 online
    by replacing the last slot (main.py:62-63). ``mode`` selects the
    draw (config.PARTICIPATION_MODES): 'perm' is the legacy O(C log C)
    full permutation, 'sparse' the O(k)-memory draw for million-client
    populations — both replayed bit-exactly by the host
    ``RoundSchedule`` (threefry is backend-deterministic)."""
    # lint: disable=FTL005 — mode is a static config string
    if mode == "sparse":
        idx = _sparse_participation(rng, num_clients, k)
    else:
        perm = jax.random.permutation(rng, num_clients)
        idx = perm[:k]
    has0 = jnp.any(idx == 0)
    force = (round_idx == 0) & ~has0
    return jnp.where(force, idx.at[k - 1].set(0), idx)


def podscale_feed_placer(mesh, k: int) -> Callable:
    """Feed placement for the pod-scale stream plane
    (docs/performance.md "Pod-scale round programs"): the big cohort
    tensors (``x``/``y``/``pre_x``/``pre_y``) go up under
    :func:`cohort_sharding` — on a multi-process mesh each host
    uploads ONLY its shard's ``[k/S, ...]`` row block (the producer
    packed nothing else), cut per-host H2D bytes and RAM by the shard
    count — while the small ``[k]`` vectors and probe batches
    replicate so the in-program cross-cohort scalars stay
    single-device-deterministic. Module-level on purpose: the
    producer thread holds the placer, and a closure over the trainer
    would keep a dropped trainer (and its jit caches) alive forever.

    Handles flat feeds, ``[R, ...]`` feed windows (detected by
    ``idx.ndim``), and the async plane's ``(feed, extras)`` pairs."""
    axis = mesh.axis_names[0]
    flat_sh = cohort_sharding(mesh)
    win_sh = NamedSharding(mesh, PartitionSpec(None, axis))
    rep = replicated_sharding(mesh)

    def put_rep(x):
        if x is None:
            return None
        if rep.is_fully_addressable:
            return jax.device_put(x, rep)
        return jax.make_array_from_process_local_data(rep, np.asarray(x))

    def place(item):
        if isinstance(item, tuple) and not isinstance(item, RoundFeed):
            feed, extras = item
            return place(feed), jax.tree.map(put_rep, extras)
        feed = item
        win = np.asarray(feed.idx).ndim == 2
        sh = win_sh if win else flat_sh

        def put_cohort(x):
            x = np.asarray(x)
            if sh.is_fully_addressable:
                return jax.device_put(x, sh)
            # multi-process: assemble the global cohort axis from this
            # host's contiguous row block
            gshape = (x.shape[0], k) + x.shape[2:] if win \
                else (k,) + x.shape[1:]
            return jax.make_array_from_process_local_data(sh, x, gshape)

        return RoundFeed(
            idx=put_rep(feed.idx), sizes=put_rep(feed.sizes),
            x=put_cohort(feed.x), y=put_cohort(feed.y),
            pre_x=put_cohort(feed.pre_x), pre_y=put_cohort(feed.pre_y),
            probe_idx=put_rep(feed.probe_idx),
            probe_x=put_rep(feed.probe_x),
            probe_y=put_rep(feed.probe_y))

    return place


class FederatedTrainer:
    """Builds and runs the jitted round program.

    The reference's ``Client.initialize`` equivalents (init_config,
    create_components, gen_aux_models — nodes/nodes.py:43-112) happen in
    :meth:`init_state`; the round loop lives in :meth:`round_fn`."""

    # the async commit plane (fedtorch_tpu.async_plane) subclasses this
    # trainer and flips the flag; constructing the BASE trainer with an
    # async config would silently run round-synchronous semantics, so
    # it refuses instead (docs/robustness.md "Asynchronous federation")
    supports_async = False
    # the dispatch-axis value this class serves from run_round — the
    # round-program cell validated at construction ('commit' on the
    # async subclass); the scan cell validates at run_rounds call time
    construction_dispatch = "round"

    def __init__(self, cfg: ExperimentConfig, model: ModelDef,
                 algorithm: FedAlgorithm, data: ClientData,
                 val_data: Optional[ClientData] = None, mesh=None,
                 gather_mode: str = "auto"):
        if cfg.federated.sync_mode == "async" and not self.supports_async:
            raise ValueError(
                "sync_mode='async' is unsupported here: the base "
                "FederatedTrainer is round-synchronous — build the "
                "trainer through the CLI or "
                "fedtorch_tpu.async_plane.AsyncFederatedTrainer; "
                "use --sync_mode sync for this class")
        self.cfg = cfg
        self.model = model
        self.algorithm = algorithm
        self.num_clients = data.num_clients
        self.batch_size = cfg.data.batch_size
        # static online-client count (online_client_rate, misc.py:14)
        self.k_online = max(
            int(cfg.federated.online_client_rate * self.num_clients), 1)
        # participation draw (config.PARTICIPATION_MODES): 'perm' =
        # legacy full permutation (bitwise-pinned), 'sparse' = the
        # O(k)-memory million-client draw; the host RoundSchedule and
        # the async scheduler replay whichever is set bit-exactly
        self.participation_mode = cfg.federated.participation_mode
        # deployment-realism round lifecycle (robustness/availability.py,
        # docs/robustness.md "Deployment realism"), sync planes only —
        # the async plane's arrivals come from its event scheduler.
        # Over-selection dispatches k' = ceil(over_select_frac * k)
        # clients (the round closes on the first k reports; the late
        # tail is masked through the accept seam). Disarmed (the
        # default), k_dispatch == k_online and every program below
        # traces byte-identically to the pre-availability engine.
        self.avail_sync = cfg.fault.avail_armed and not self.supports_async
        self.k_dispatch = max(math.ceil(
            cfg.fault.over_select_frac * self.k_online), self.k_online) \
            if self.avail_sync else self.k_online

        # static local-step count per round (flow_utils.py:33-40 epoch /
        # local_step sync modes; epoch mode sizes the scan for the max
        # client — shorter clients early-exit via masking in round_fn)
        if cfg.federated.sync_type == "epoch":
            nb_max = math.ceil(data.n_max / self.batch_size)
            self.local_steps = nb_max * cfg.federated.num_epochs_per_comm
        else:
            self.local_steps = max(cfg.train.local_step, 1)
        self.epoch_sync = cfg.federated.sync_type == "epoch"

        # fault layer (docs/robustness.md): all gating is STATIC config,
        # so with faults off the traced round program is unchanged.
        # Straggler cuts reuse the epoch-sync freeze mask, which must
        # then also run in local_step mode.
        self.fault = cfg.fault
        self.chaos_on = cfg.fault.chaos_enabled
        self.guard_on = cfg.fault.guard_updates
        self.mask_steps = self.epoch_sync or cfg.fault.straggler_rate > 0.0
        # robust aggregation (robustness/aggregators.py): the rule is
        # static config, so 'mean' (default) traces the aggregation
        # seam byte-identically to the pre-robust engine. 'norm_bound'
        # carries a params-shaped server momentum: server.aux is
        # wrapped {'alg': <algorithm aux>, 'norm_bound_m': <tree>} by
        # init_state and unwrapped at the top of _round_core (the async
        # ring wraps OUTSIDE this, so the two compose).
        self.robust_rule = cfg.fault.robust_agg
        self.robust_momentum = self.robust_rule == "norm_bound"
        # federation-plane cohort statistics (telemetry.cohort_stats,
        # docs/observability.md "Federation plane"): static config —
        # off (default) the round program is byte-identical to the
        # pre-cohort engine (the extra RoundMetrics fields stay None,
        # contributing zero outputs); on, the aggregation seam emits
        # per-client masks/suspicion + the heterogeneity gauges and
        # they ride the loop's one batched fetch into the ledger
        self.cohort_stats = bool(cfg.telemetry.cohort_stats)
        # privacy plane (robustness/privacy.py): static config — off
        # (default) the round program is HLO byte-identical (no wrap,
        # no extra RoundMetrics outputs); on, server.aux is wrapped
        # {'alg': <aux>, 'dp_noise_scale': f32[]} by init_state (DP x
        # norm_bound is refused at finalize, so the two wraps never
        # coexist; the async ring still wraps OUTSIDE) and _round_core
        # clips each client to dp_clip_norm before the robust rule and
        # noises the released estimate after it. dp_noise_scale is
        # DATA (1.0 armed, 0.0 after a budget 'degrade') so exhaustion
        # never retraces.
        self.dp_on = bool(cfg.fault.dp_armed)
        self.dp_clip_norm = float(cfg.fault.dp_clip_norm)
        self.dp_noise_multiplier = float(cfg.fault.dp_noise_multiplier)

        # data source + gather mode: the refusals (explicit 'shard' on
        # a packed-row program, feed-source algorithm preconditions,
        # 'batch' under a full-loss algorithm) all live in the ONE
        # round-program cell validator (parallel/round_program.py) —
        # the builder validation call below raises them by cell name.
        self.data_plane = cfg.data.data_plane
        self.has_val = val_data is not None
        # the EXPLICIT (pre-resolution) mode is what the cell validator
        # judges; the resolved mode drives the in-program gather
        self.explicit_gather_mode = gather_mode
        self.gather_mode = resolve_gather_mode(
            gather_mode, algorithm=algorithm,
            data_plane=self.data_plane, local_steps=self.local_steps,
            batch_size=self.batch_size, n_max=data.n_max,
            client_shards=int(getattr(cfg.mesh, "client_shards", 0)
                              or 0))
        # train-time flip+crop augmentation for image batches (the
        # reference's cifar transform, prepare_data.py:29-35);
        # ClientData x is [clients, N, H, W, C] for image datasets
        self.augment = bool(cfg.data.augment) and data.x.ndim == 5
        # a token model trains on rows of token ids ([C, n, T] store):
        # what a round trains, for the row's ``tokens_trained`` counter
        self.row_tokens = int(data.x.shape[2]) \
            if is_token_model(model) and data.x.ndim == 3 else 0
        self.tokens_per_round = self.k_online * self.local_steps \
            * self.batch_size * self.row_tokens
        num_epochs = cfg.train.num_epochs or 1
        self.schedule: LRSchedule = compile_schedule(
            cfg.lr_schedule, cfg.optim, num_epochs,
            world_size=self.num_clients)
        self.criterion = make_criterion(model.is_regression)
        algorithm.setup(data)
        algorithm.bind(model, self.criterion)
        algorithm.local_steps_per_round = self.local_steps
        algorithm.k_online = self.k_online
        self.mesh = mesh if mesh is not None else make_mesh(
            cfg.mesh, self.num_clients)
        algorithm.mesh_devices = int(self.mesh.devices.size)
        # pod-scale client-axis sharding (docs/performance.md
        # "Pod-scale round programs"): client_shards is the EFFECTIVE
        # shard count S (the 2-D mesh's leading axis; 1 on a legacy
        # mesh); podscale_armed also covers mesh.client_shards == 1 —
        # the unsharded twin that runs the same grouped hierarchical
        # aggregation seam, which every sharded cell is pinned
        # bitwise against. Disarmed (0, the default) traces the
        # legacy program byte-identically.
        self.client_shards = mesh_client_shards(self.mesh)
        self.podscale_armed = (
            self.client_shards > 1
            or int(getattr(cfg.mesh, "client_shards", 0) or 0) >= 1)
        # static [G, P] bytes the seam's one all-gather moves per
        # round — stashed at first trace (podscale only), emitted via
        # telemetry_gauges
        self._allreduce_bytes: Optional[float] = None
        # how the cohort's clients run (``mesh.client_fusion``):
        # stacked under ``vmap`` ('auto' is 'vmap' today, ROADMAP
        # Design 5), or 'sequential', one after another into a running
        # fold (_round_core_sequential); what that cannot serve is
        # refused by round_program.validate_cell
        self.client_fusion = "sequential" \
            if cfg.mesh.client_fusion == "sequential" else "vmap"
        # the keys of a token model's own gauges on the round's row
        # (models/common.py ``is_token_model``): the sequential round
        # asks the loss for its parts and hands them to the model
        self.gauge_names = tuple(getattr(model, "gauge_names", ())) \
            if self.tokens_per_round \
            and self.client_fusion == "sequential" else ()
        # the round-program builder (parallel/round_program.py): the
        # ONE place programs are composed and cells are refused. The
        # construction-time dispatch ('round' here, 'commit' on the
        # async subclass) validates now; the scan cell validates when
        # run_rounds is actually called.
        self.programs = RoundProgramBuilder(self)
        self.programs.validate(self.construction_dispatch)
        if algorithm.needs_val_batch and val_data is None:
            raise ValueError(
                f"{algorithm.name} needs per-client validation batches; "
                "pass FederatedData.val (cfg.federated.personal builds it)")
        # the client axis is padded up to a multiple of the mesh size with
        # inert (never-sampled, size-0) clients so EVERY device holds an
        # equal shard — no chip idles when num_clients has no large
        # divisor (SURVEY.md §7 [cores, clients_per_core] layout)
        self.padded_clients = padded_client_count(self.num_clients,
                                                  self.mesh)
        if self.data_plane == "stream":
            # HBM never sees the client store: it stays a host numpy
            # array (population bounded by host RAM, not HBM) and each
            # round receives only its double-buffered [k, K*B, ...]
            # feed. Client STATE still shards over the mesh as usual —
            # state is params-sized, data is the big thing.
            if cfg.data.store == "mmap":
                # disk-backed population (docs/performance.md "The
                # million-client store"): host residency is O(feed),
                # the shard files page in on demand
                store = MmapClientStore(cfg.data.store_dir)
                if (store.num_clients != self.num_clients
                        or store.n_max != data.n_max):
                    raise ValueError(
                        f"mmap client store at {cfg.data.store_dir!r} "
                        f"holds [{store.num_clients}, {store.n_max}] "
                        "clients x rows but the run's data is "
                        f"[{self.num_clients}, {data.n_max}]")
                self.host_store = store
            else:
                self.host_store = HostClientStore(data)
            self.data = None
            self.val_data = None
        else:
            self.host_store = None
            with telemetry.span("data.h2d") as h2d:
                self.data = shard_clients(
                    pad_client_axis(data, self.padded_clients), self.mesh)
                self.val_data = shard_clients(
                    pad_client_axis(val_data, self.padded_clients),
                    self.mesh) if val_data is not None else None
                if h2d is not telemetry.NULL_SPAN:
                    # a recorded span ends when the store is on the
                    # device, not when the copy is dispatched; with no
                    # recorder nothing waits and the copy overlaps the
                    # state's build
                    jax.block_until_ready((self.data, self.val_data))
        # lazily-started feed producer (stream plane only); see
        # _next_stream_feed / invalidate_stream
        self._stream: Optional[StreamFeedProducer] = None
        self._stream_finalizer = None
        # producer rebuilds survived so far (docs/robustness.md "Host
        # plane"): a dead producer is torn down and rebuilt through
        # the invalidate_stream resync instead of aborting the run
        self._stream_rebuilds = 0
        # trace-event instrumentation (utils.tracing): the sentinel
        # test asserts this program traces exactly once per trainer —
        # "static config => unchanged traced program" is the contract
        # both the chaos layer and the bench path rely on
        self.trace_name = f"federated.round[{algorithm.name}]"
        self._round_jit = jax.jit(
            instrument_trace(self.trace_name, self.round_fn),
            donate_argnums=(0, 1))
        # the streaming twin takes the per-round feed instead of the
        # full data pytree; feed shapes are static, so it too traces
        # exactly once (sentinel-pinned in tests/test_streaming.py)
        self.stream_trace_name = \
            f"federated.round_stream[{algorithm.name}]"
        self._round_stream_jit = jax.jit(
            instrument_trace(self.stream_trace_name,
                             self.round_stream_fn),
            donate_argnums=(0, 1)) if self.data_plane == "stream" \
            else None
        self._rounds_jit: dict = {}  # num_rounds -> jitted scan driver
        # the round's log scalars as ONE program handing over ONE
        # array (round_host_fetch): built here, traced at the first
        # fetch and once more for each set of metrics leaves it meets
        # (cohort stats / DP gauges armed); the result replicated, so
        # every process can fetch it
        self.scalars_trace_name = "federated.round_scalars"
        # lint: disable=FTL004 — the epochs and metrics handed in live on
        self._scalars_jit = jax.jit(
            instrument_trace(self.scalars_trace_name,
                             self._round_scalars),
            out_shardings=replicated_sharding(self.mesh))
        # the schedule enters that program as an ARGUMENT, placed on
        # the mesh once: closed over, its arrays are constants XLA
        # folds (a division by a constant becomes a product with its
        # reciprocal) and the logged lr leaves lr_at's eager value by
        # one place in the last (tests/test_round_scalars.py)
        self._schedule_dev = jax.device_put(
            self.schedule, replicated_sharding(self.mesh))
        # preemption stop-flag plumbing (robustness/preemption.py):
        # attach_stop_signal folds a cross-host-agreed stop flag into
        # round_host_fetch; nothing here touches the round program
        self._stop_signal: Optional[Callable[[], bool]] = None
        self._stop_reduce = None  # lazily-jitted cross-process max

    # -- state ----------------------------------------------------------
    def init_state(self, rng: jax.Array) -> Tuple[ServerState, ClientState]:
        rng, init_rng = jax.random.split(rng)
        params = self.model.init(init_rng)
        sequential = self.client_fusion == "sequential"
        server = ServerState(
            params=params,
            opt=optim.init_opt_state(params, self.cfg.optim,
                                     lean=sequential),
            aux=self.algorithm.init_server_aux(params, self.num_clients),
            round=jnp.zeros((), jnp.int32),
            rng=rng)
        # client states cover the PADDED axis so they shard evenly; the
        # padding tail is dead weight that is never gathered by idx
        C = self.padded_clients
        if sequential:
            # every client starts its round from the server's
            # parameters and nothing reads its own back, so the state
            # holds no [C]-leading parameter-sized leaf: the counters
            # alone (validate_cell refused what needs more)
            clients = ClientState(
                params=(), opt=optim.init_opt_state((), self.cfg.optim,
                                                    lean=True),
                aux=(), epoch=jnp.zeros((C,)),
                local_index=jnp.zeros((C,), jnp.int32))
            return replicate(server, self.mesh), \
                shard_clients(clients, self.mesh)

        def one_client(_):
            return ClientState(
                params=params,
                opt=optim.init_opt_state(params, self.cfg.optim),
                aux=self.algorithm.init_client_aux(params),
                epoch=jnp.zeros(()),
                local_index=jnp.zeros((), jnp.int32))

        clients = jax.vmap(one_client)(jnp.arange(C))
        if self.robust_momentum:
            # the norm_bound center starts at zero (first round clips
            # toward the origin at the median-update radius)
            server = server._replace(aux={
                "alg": server.aux,
                "norm_bound_m": tree_zeros_like(params)})
        if self.dp_on:
            # noise_scale is DATA: the budget lifecycle's 'degrade'
            # flips it to 0.0 in place (dp_set_noise_scale) — same
            # program, no retrace
            server = server._replace(aux={
                "alg": server.aux,
                "dp_noise_scale": jnp.asarray(1.0, jnp.float32)})
        return replicate(server, self.mesh), \
            shard_clients(clients, self.mesh)

    # -- one communication round -----------------------------------------
    def round_fn(self, server: ServerState, clients: ClientState,
                 data: ClientData, val_data: Optional[ClientData] = None):
        """Device-resident data plane: the full ``[C, n_max, ...]``
        store is a program input and the round's online rows are
        gathered IN-program. 'batch' takes them one cohort member at a
        time (``gather_client_rows``: that client's shard off the
        leading axis, its ``K*B`` rows from the shard's flat view), so
        the program reads k shards and never the store; 'shard' takes
        the k whole shards and selects rows per step. The streaming
        twin (:meth:`round_stream_fn`) receives the same rows as a
        host-packed feed; both funnel into :meth:`_round_core`, so the
        two planes cannot diverge."""
        alg = self.algorithm
        K, B, C = self.local_steps, self.batch_size, self.num_clients
        batch_mode = self.gather_mode == "batch"
        with jax.named_scope("fed.select"):
            rng_round = jax.random.fold_in(server.rng, server.round)
            rng_sample, rng_train = jax.random.split(rng_round)

            # participation hooks read the ALGORITHM aux (DRFA's
            # lambda), not the norm_bound momentum wrap
            part_aux = server.aux["alg"] \
                if (self.robust_momentum or self.dp_on) else server.aux
            idx = alg.participation(rng_sample, C, self.k_dispatch,
                                    server.round, part_aux)
            if idx is None:
                idx = participation_indices(
                    rng_sample, C, self.k_dispatch, server.round,
                    mode=self.participation_mode)
            on_sizes = jnp.take(data.sizes, idx)
            rngs = jax.random.split(rng_train, self.k_dispatch)
            if batch_mode:
                # the [k, K*B] storage rows the round trains on, which
                # gather_client_rows then takes shard by shard.
                # round_row_plan (data/batching.py) is the SHARED
                # batch-order definition — the host feed packer calls
                # the same function, which is what makes the streaming
                # plane's bitwise parity hold.
                rows = jax.vmap(lambda r, s: round_row_plan(
                    r, s, data.x.shape[1], K * B))(rngs, on_sizes)
                # pod-scale: pin the row plan REPLICATED. The seam's
                # cohort sharding otherwise propagates backward through
                # the gather into round_row_plan's argsort, and a
                # cross-device partitioned sort is not bitwise-stable
                # across shard counts — the one S-variant lowering in
                # the whole program (no-op when podscale is disarmed)
                rows = self._replicate_cohort(rows)

        with jax.named_scope("fed.gather"):
            if batch_mode:
                on_x, on_y = gather_client_rows(
                    (data.x, data.y), idx, rows)
            else:
                # whole shards; rows are selected per step inside the
                # vmap so nothing larger than the shard is ever
                # materialized
                on_x = jnp.take(data.x, idx, axis=0)
                on_y = jnp.take(data.y, idx, axis=0)

        # the val stream makes its own shard-vs-rows decision: val shards
        # are typically much smaller than train shards, so K*B rows can
        # exceed the shard itself
        val_batch_mode = (batch_mode and val_data is not None
                          and K * B < val_data.x.shape[1])
        if val_data is not None:
            with jax.named_scope("fed.select"):
                on_vsizes = jnp.take(val_data.sizes, idx)
                if val_batch_mode:
                    vrows = jax.vmap(lambda r, s: round_row_plan(
                        r, s, val_data.x.shape[1], K * B,
                        VAL_FOLD))(rngs, on_vsizes)
                    vrows = self._replicate_cohort(vrows)
        with jax.named_scope("fed.gather"):
            if val_data is None:
                # unused placeholders keep the vmapped signature static
                on_vx, on_vy = on_x[:, :1], on_y[:, :1]
                on_vsizes = jnp.ones_like(on_sizes)
            elif val_batch_mode:
                on_vx, on_vy = gather_client_rows(
                    (val_data.x, val_data.y), idx, vrows)
            else:
                on_vx = jnp.take(val_data.x, idx, axis=0)
                on_vy = jnp.take(val_data.y, idx, axis=0)

            # the pre_round hook always sees each client's first B
            # storage-order rows, independent of gather mode (so mode
            # choice cannot change hook numerics, e.g. APFL's alpha)
            pre_x, pre_y = gather_client_rows(
                (data.x, data.y), idx,
                jnp.broadcast_to(jnp.arange(B), idx.shape + (B,)))
        return self._round_core(
            server, clients, idx, on_x, on_y, on_vx, on_vy, on_sizes,
            on_vsizes, pre_x, pre_y, rng_round, rngs,
            batch_mode=batch_mode, val_batch_mode=val_batch_mode,
            data=data)

    def round_stream_fn(self, server: ServerState, clients: ClientState,
                        feed: RoundFeed):
        """Streaming data plane: the round program takes the host-packed
        feed — the K online clients' pre-selected ``[k, K*B, ...]``
        rows — instead of the full data pytree. The PRNG chain below is
        byte-for-byte the device plane's (``rng_sample`` is drawn and
        discarded: the host already replayed participation from it), so
        dropout/augmentation/chaos streams line up and the trajectories
        match the device plane bitwise (tests/test_streaming.py)."""
        with jax.named_scope("fed.select"):
            rng_round = jax.random.fold_in(server.rng, server.round)
            _rng_sample, rng_train = jax.random.split(rng_round)
            rngs = jax.random.split(rng_train, self.k_dispatch)
        # no streamed val plane (gated in __init__): mirror the device
        # path's val_data-None placeholders exactly
        with jax.named_scope("fed.gather"):
            on_vx, on_vy = feed.x[:, :1], feed.y[:, :1]
            on_vsizes = jnp.ones_like(feed.sizes)
        return self._round_core(
            server, clients, feed.idx, feed.x, feed.y, on_vx, on_vy,
            feed.sizes, on_vsizes, feed.pre_x, feed.pre_y, rng_round,
            rngs, batch_mode=self.gather_mode == "batch",
            val_batch_mode=False,
            probe=feed if feed.probe_idx is not None else None)

    # -- pod-scale cohort layout (mesh.client_shards) ---------------------
    def _shard_cohort(self, tree):
        """Constrain ``[k, ...]`` cohort tensors over the client-shard
        axis (no-op when podscale is disarmed — the legacy program is
        byte-identical). Per-client compute under the constraint is
        elementwise-independent across clients, so values are bitwise
        invariant to the shard count."""
        if not self.podscale_armed:
            return tree
        sh = cohort_sharding(self.mesh)
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, sh), tree)

    def _replicate_cohort(self, tree):
        """Constrain small ``[k]`` cohort vectors replicated (no-op
        when podscale is disarmed). This is the other half of the
        bitwise bar: every cross-cohort float reduction outside the
        hierarchical seam (weight renormalization, metric sums) then
        lowers to the same single-device reduce at every shard count,
        so its association can never depend on S."""
        if not self.podscale_armed:
            return tree
        sh = replicated_sharding(self.mesh)
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, sh), tree)

    def _round_core(self, server: ServerState, clients: ClientState,
                    idx, on_x, on_y, on_vx, on_vy, on_sizes, on_vsizes,
                    pre_x, pre_y, rng_round, rngs, *, batch_mode: bool,
                    val_batch_mode: bool, data=None, base_params=None,
                    base_aux=None, weight_scale=None, plan=None,
                    probe=None):
        """The round program proper, data-plane agnostic: everything
        after the online rows exist — local loops, chaos/guards,
        aggregation, server step, state scatter, metrics. ``on_x`` is
        either the packed rows [k, K*B, ...] (``batch_mode``) or whole
        client shards [k, n_max, ...]. ``data`` (the full store) is
        only threaded for ``post_round_global`` (DRFA's dual phase) —
        the streaming plane passes None and threads ``probe`` (the
        feed with its host-packed probe batches) instead, dispatching
        ``post_round_global_feed``.

        COMMIT-DISPATCH SEAM (parallel/round_program.py — the commit
        member of the round-program family): the keyword overrides
        let a caller re-dispatch this same core as an asynchronous
        buffered COMMIT instead of a synchronous round —
        ``base_params``/``base_aux`` thread a PER-CLIENT [k] server
        snapshot (params + server aux) through every local-loop hook
        (each buffered client trained against a possibly-stale commit
        version), ``weight_scale`` composes staleness weights into the
        aggregation weights before the guard renormalization, and
        ``plan`` substitutes a caller-built chaos plan (async stragglers
        are arrival DELAYS, not step cuts). All four default to None,
        which traces exactly the synchronous program."""
        if self.client_fusion == "sequential":
            # validate_cell refused the commit seam, chaos, guards,
            # robust rules, DP, val streams and shard gathers by name
            return self._round_core_sequential(
                server, clients, idx, on_x, on_y, on_sizes, rng_round,
                rngs, data=data, probe=probe)
        # norm_bound robust aggregation carries its server momentum in
        # server.aux ({'alg': ..., 'norm_bound_m': ...}); every
        # algorithm hook below reads the unwrapped ALG aux. The async
        # ring wraps outside this layer, so a stacked base_aux from the
        # snapshot ring unwraps the same way.
        if self.robust_momentum:
            robust_m = server.aux["norm_bound_m"]
            server = server._replace(aux=server.aux["alg"])
            if base_aux is not None:
                base_aux = base_aux["alg"]
        else:
            robust_m = None
        # the DP wrap ({'alg': ..., 'dp_noise_scale': f32[]}) unwraps
        # at the same seam (DP x norm_bound refused at finalize, so
        # at most one wrap is present under the async ring)
        if self.dp_on:
            dp_scale = server.aux["dp_noise_scale"]
            server = server._replace(aux=server.aux["alg"])
            if base_aux is not None:
                base_aux = base_aux["alg"]
        else:
            dp_scale = None
        # pod-scale cohort layout, pinned BEFORE any cross-client op:
        # big per-client tensors shard over the client-shard axis (each
        # shard group executes only its k/S clients' local loops),
        # small [k] vectors replicate (docstrings above)
        if self.podscale_armed:
            on_x, on_y, on_vx, on_vy, pre_x, pre_y = self._shard_cohort(
                (on_x, on_y, on_vx, on_vy, pre_x, pre_y))
            idx, on_sizes, on_vsizes = self._replicate_cohort(
                (idx, on_sizes, on_vsizes))
        cfg, model, alg = self.cfg, self.model, self.algorithm
        K, B, C = self.local_steps, self.batch_size, self.num_clients
        # the online axis length: k_online for the sync planes, the
        # commit buffer size m for the async plane
        k = idx.shape[0]
        with jax.named_scope("fed.select"):
            num_online_eff = num_online_effective(idx)
            weights = alg.client_weights(server.aux, idx, num_online_eff,
                                         on_sizes)
            if weight_scale is not None:
                # staleness weighting (async_plane/staleness.py): composed
                # INTO the aggregation weights, so the guard renormalization
                # below redistributes exactly the composed weight
                weights = weights * weight_scale
            weights = self._replicate_cohort(weights)

        with jax.named_scope("fed.guard"):
            # deterministic chaos schedule for this round (crash/straggler/
            # poison masks over the online clients) — its own fold of the
            # round key, so fault-free streams are untouched
            flt = self.fault
            if plan is None:
                plan = draw_chaos_plan(
                    jax.random.fold_in(rng_round, flt.chaos_salt),
                    k, flt) if self.chaos_on else no_chaos_plan(k)
            if flt.byzantine_rate > 0.0:
                # the adversarial cohort is FIXED per run (server.rng is
                # threaded unchanged through every round, so the fold is
                # round-independent); the plan carries its online slice.
                # Applies to caller-built plans too (the async commit).
                cohort = byzantine_cohort_mask(
                    jax.random.fold_in(server.rng, BYZ_COHORT_FOLD),
                    C, flt.byzantine_rate)
                plan = plan._replace(byzantine=jnp.take(cohort, idx))

            # deployment-realism round lifecycle (robustness/availability.py
            # sync planes only — the async plane's arrivals come from its
            # event scheduler): per-dispatched-client arrival delays and
            # mid-round dropouts, the round closing on its first k_online
            # arrivals. Static gating: disarmed traces the exact
            # pre-availability program.
            avail_ok = avail_drop = avail_miss = None
            if self.avail_sync:
                avail_ok, avail_drop, avail_miss = sync_lifecycle(
                    server.rng, rng_round, idx, server.round, flt,
                    self.k_online)

        with jax.named_scope("fed.gather"):
            # gather online-client state (the per-round new_group)
            take = lambda t: jax.tree.map(
                lambda x: jnp.take(x, idx, axis=0), t)
            on_clients = self._shard_cohort(take(clients))

        with jax.named_scope("fed.pre_round"):
            # cross-client pre-round hook (APFL adaptive alpha,
            # apfl.py:119-123)
            on_lrs = jax.vmap(lambda e: lr_at(self.schedule, e))(
                on_clients.epoch)
            on_aux0 = alg.pre_round(on_clients.aux, server=server, x=pre_x,
                                    y=pre_y, sizes=on_sizes, lr=on_lrs,
                                    rng=rng_round)
            # round-start state, kept for crashed clients: fail-stop means
            # everything after round start (incl. the pre_round aux write)
            # is lost on the client
            on_clients0 = on_clients
            on_clients = on_clients._replace(aux=on_aux0)

        def client_round(cstate: ClientState, x, y, vx, vy, size, vsize,
                         weight, rng_c, bscale, base_p, base_a):
            # batch mode: x/y are the round's pre-selected rows [K*B, ...]
            # shard mode: x/y are whole shards [n_max, ...], rows picked
            # per step (nothing larger than the shard is materialized).
            # base_p/base_a are THIS client's server snapshot — the live
            # server state on the sync planes (vmap in_axes=None), its
            # dispatch-time commit version on the async plane
            with jax.named_scope("fed.local_steps"):
                nb = jnp.ceil(size / B)  # batches per local epoch
                server_params = base_p
                carry0 = model.init_carry(B)

                full_loss = None
                if alg.needs_full_loss:
                    # qFFL: F_k = SUM of per-batch mean losses over the
                    # client's full data on the incoming server model
                    # (centered/main.py:62-72 accumulates loss.item() per
                    # batch — the sum scales with the client's batch count);
                    # shard mode is enforced so x IS the whole shard here
                    n_full = -(-x.shape[0] // B)

                    def floss(carry, i):
                        frows = i * B + jnp.arange(B)
                        m = (frows < size).astype(jnp.float32)
                        xb, yb = x[frows % x.shape[0]], y[frows % x.shape[0]]
                        if model.is_recurrent:
                            logits, _ = model.apply(server_params, xb,
                                                    carry=carry0)
                        else:
                            logits = model.apply(server_params, xb)
                        per = per_sample_loss(logits, yb, model.is_regression)
                        batch_mean = jnp.sum(per * m) / jnp.maximum(
                            jnp.sum(m), 1.0)
                        has_real = (jnp.sum(m) > 0).astype(jnp.float32)
                        return carry, batch_mean * has_real

                    _, batch_means = jax.lax.scan(floss, 0, jnp.arange(n_full))
                    full_loss = jnp.sum(batch_means)

                if not batch_mode:
                    perm = epoch_permutation(jax.random.fold_in(rng_c, 0),
                                             size, x.shape[0])
                if alg.needs_val_batch and not val_batch_mode:
                    vperm = epoch_permutation(jax.random.fold_in(rng_c,
                                                                 VAL_FOLD),
                                              vsize, vx.shape[0])

                # per-client early exit (is_sync_fed, flow_utils.py:33-40):
                # in epoch-sync mode a client stops after ITS OWN epoch
                # budget ceil(size/B)*E steps; the scan keeps running in
                # lockstep but frozen clients' state and metrics don't move.
                # The budget is ALSO every hook's effective local_steps (so
                # scaffold/fedgate control updates divide by the steps the
                # client actually took) and feeds step-indexed algorithm
                # logic (PerFedMe's sync pull, DRFA's snapshot clamp).
                step_budget = (nb.astype(jnp.int32)
                               * self.cfg.federated.num_epochs_per_comm) \
                    if self.epoch_sync else jnp.asarray(K, jnp.int32)
                if flt.straggler_rate > 0.0:
                    # straggler chaos: the client misses the round deadline
                    # after a fraction of ITS OWN budget (>= 1 step); rides
                    # the same freeze mask as epoch-sync early exit
                    step_budget = jnp.maximum(jnp.ceil(
                        step_budget.astype(jnp.float32) * bscale), 1.0) \
                        .astype(jnp.int32)

            def step(carry, k):
                params, opt, aux, epoch, li, rnn_carry = carry
                active = (k < step_budget) if self.mask_steps \
                    else jnp.asarray(True)
                lr = lr_at(self.schedule, epoch)
                if batch_mode:
                    bx = jax.lax.dynamic_slice_in_dim(x, k * B, B)
                    by = jax.lax.dynamic_slice_in_dim(y, k * B, B)
                else:
                    bx, by = take_batch(x, y, perm, size, k, B)
                if alg.needs_val_batch:
                    if val_batch_mode:
                        bval_x = jax.lax.dynamic_slice_in_dim(vx, k * B, B)
                        bval_y = jax.lax.dynamic_slice_in_dim(vy, k * B, B)
                    else:
                        bval_x, bval_y = take_batch(vx, vy, vperm, vsize,
                                                    k, B)
                else:
                    bval_x = bval_y = None
                if self.augment:
                    # separate stream from drop_rng's fold(k+1): derive
                    # from a disjoint parent key (folds are uint32; K can
                    # never reach 2^31 steps) so the two cannot collide
                    with jax.named_scope("fed.augment"):
                        aug_parent = jax.random.fold_in(rng_c, 0x7FFFFFFF)
                        bx = augment_image_batch(
                            jax.random.fold_in(aug_parent, k), bx)
                drop_rng = jax.random.fold_in(rng_c, k + 1)
                n_params, n_opt, n_aux, n_rnn, loss, acc = alg.local_step(
                    params=params, opt=opt, client_aux=aux,
                    rnn_carry=rnn_carry, server_params=server_params,
                    server_aux=base_a, bx=bx, by=by, bval_x=bval_x,
                    bval_y=bval_y, lr=lr, rng=drop_rng, step_idx=k,
                    local_index=li, step_budget=step_budget)
                if self.mask_steps:
                    sel = lambda n, o: jax.tree.map(
                        lambda a, b: jnp.where(active, a, b), n, o)
                    n_params, n_opt = sel(n_params, params), sel(n_opt, opt)
                    n_aux, n_rnn = sel(n_aux, aux), sel(n_rnn, rnn_carry)
                af = active.astype(jnp.float32)
                return (n_params, n_opt, n_aux, epoch + af / nb,
                        li + active.astype(li.dtype), n_rnn), \
                    (loss, acc, af)

            with jax.named_scope("fed.local_steps"):
                init = (server_params, cstate.opt, cstate.aux,
                        cstate.epoch, cstate.local_index, carry0)
                (params, opt, aux, epoch, li, _), (losses, accs, act) = \
                    jax.lax.scan(step, init, jnp.arange(K))

                delta = tree_sub(server_params, params)
                lr_end = lr_at(self.schedule, epoch)
            with jax.named_scope("fed.wire"):
                payload, aux = alg.client_payload(
                    delta=delta, client_aux=aux, params=params,
                    server_params=server_params, server_aux=base_a,
                    lr=lr_end, local_steps=step_budget, weight=weight,
                    full_loss=full_loss)
            new_state = ClientState(params=params, opt=opt, aux=aux,
                                    epoch=epoch, local_index=li)
            # metrics over the steps the client actually took (frozen
            # early-exit steps contribute nothing)
            with jax.named_scope("fed.metrics"):
                n_act = jnp.maximum(jnp.sum(act), 1.0)
                return payload, delta, new_state, (
                    jnp.sum(losses * act) / n_act,
                    jnp.sum(accs * act) / n_act)

        # the per-client server snapshot: stacked [k] trees on the
        # async commit plane, the live server state broadcast
        # (in_axes=None — vmap treats it exactly like the previous
        # closure capture, so the sync program is unchanged)
        stacked_base = base_params is not None
        base_p_in = base_params if stacked_base else server.params
        base_a_in = base_aux if stacked_base else server.aux
        base_ax = 0 if stacked_base else None
        payloads, deltas, new_on_clients, (losses, accs) = jax.vmap(
            client_round,
            in_axes=(0,) * 10 + (base_ax, base_ax)
        )(on_clients, on_x, on_y, on_vx, on_vy,
          on_sizes, on_vsizes, weights, rngs,
          plan.budget_scale, base_p_in, base_a_in)
        # pod-scale: each shard group leaves the client loops holding
        # its k/S clients' payloads/state; per-client scalars replicate
        # so downstream metric sums stay shard-count invariant
        payloads, deltas, new_on_clients = self._shard_cohort(
            (payloads, deltas, new_on_clients))
        losses, accs = self._replicate_cohort((losses, accs))

        with jax.named_scope("fed.guard"):
            # wire-level adversaries and faults: the clients' local state
            # stays sane (``deltas`` itself must stay clean: client_post
            # consumes it for persistent aux updates like FedGATE's
            # tracking variate); ``wire_deltas`` is what the guards judge —
            # the corrupted view the server saw. The byzantine swap comes
            # FIRST (an adversary crafts what it sends, then the wire
            # format applies like any client's); nan poison last (a fried
            # wire trumps whatever was on it).
            wire_deltas = deltas
            byz_count = jnp.zeros(())
            if flt.byzantine_rate > 0.0:
                byz_rng = jax.random.fold_in(
                    jax.random.fold_in(rng_round, flt.chaos_salt),
                    BYZ_NOISE_FOLD)
                wire_deltas, payloads = apply_byzantine(
                    plan, wire_deltas, payloads, weights, byz_rng, flt)
                # count uploads that actually REACH the server: a cohort
                # member that also crash-chaosed never uploads, so its
                # crafted payload is not an injected attack
                byz_count = jnp.sum(plan.byzantine * plan.survive)
            if flt.nan_inject_rate > 0.0:
                wire_deltas = poison_tree(wire_deltas, plan.nan_inject)

        with jax.named_scope("fed.wire"):
            # uplink wire format on the stacked [k] payload axis (per-client
            # quantization via the pallas client-grid kernel — outside the
            # vmap, where pallas_call can actually run)
            payloads = alg.payload_batch_transform(payloads)
        with jax.named_scope("fed.guard"):
            if flt.nan_inject_rate > 0.0:
                payloads = poison_tree(payloads, plan.nan_inject)

            # server-side screening: crashed clients never arrive; with
            # guards on, non-finite / norm-exploded deltas are rejected or
            # clipped (guards.py). ``accept`` is the final aggregation mask
            # and the surviving aggregation weight is renormalized so the
            # server step keeps its fault-free magnitude.
            rejected = clipped = jnp.zeros(())
            # reporters this round: chaos survival AND (availability plane
            # armed) arrival by the deadline — a dropout or late report
            # never reaches the server, so it is excluded BEFORE the
            # guards (it must not influence the median norm) and before
            # the robust rule; its weight renormalizes away below exactly
            # like a crashed client's.
            survive = plan.survive if avail_ok is None \
                else plan.survive * avail_ok.astype(jnp.float32)
            if self.guard_on:
                payloads, report = screen_payloads(wire_deltas, payloads,
                                                   survive, flt)
                accept, rejected, clipped = (report.accept, report.rejected,
                                             report.clipped)
            elif self.chaos_on or self.avail_sync:
                accept = survive
                payloads = tree_where(accept, payloads,
                                      tree_zeros_like(payloads))
            else:
                accept = None
            if accept is not None:
                # the accept mask feeds the renormalization sums below —
                # replicated, its weighted reductions keep one association
                accept = self._replicate_cohort(accept)
            if self.avail_sync and flt.byzantine_rate > 0.0:
                # recount attacks that actually reached the server: a
                # cohort member that dropped out or missed the deadline
                # never delivered its crafted upload
                byz_count = jnp.sum(plan.byzantine * survive)

        with jax.named_scope("fed.guard"):
            # privacy plane, clip half (robustness/privacy.py): per-client
            # L2 clip to dp_clip_norm BEFORE the robust rule sees the
            # payloads — the clip bounds every client's sensitivity no
            # matter what the rule (or the cohort statistics below) then
            # does with them. Composition order (pinned, docs/robustness.md
            # "Privacy plane"): accept mask -> DP clip -> robust rule
            # (x staleness weights) -> DP noise on the released estimate.
            dp_clipped_frac = None
            if self.dp_on:
                payloads, dp_clipped_frac = dp_clip_payloads(
                    payloads, weights, accept, self.dp_clip_norm)

        # the aggregation seam: either the plain weighted sum (the
        # pre-robust engine, kept verbatim so --robust_agg mean stays
        # bitwise-identical) or a byzantine-robust rule over the same
        # stacked payloads (robustness/aggregators.py), composing AFTER
        # the chaos/guard accept mask and the async staleness weights;
        # the downlink wire-format transform applies ONCE either way so
        # the server step and client_post see the same sum
        robust_selected = robust_trimmed = jnp.zeros(())
        new_robust_m = robust_m
        # per-client cohort evidence at the seam (None = stats off —
        # the default traces the exact pre-cohort program)
        cohort = None
        if self.robust_rule != "mean":
            with jax.named_scope("fed.guard"):
                accept_f = accept if accept is not None else jnp.ones((k,))
                payload_sum, new_robust_m, rreport = robust_aggregate(
                    self.robust_rule, payloads, weights, accept_f, flt,
                    momentum=robust_m, per_client=self.cohort_stats)
                robust_selected = rreport.selected
                robust_trimmed = rreport.trimmed
                if self.cohort_stats:
                    # the rule's own evidence (krum scores, trim fractions,
                    # clip ratios) is the suspicion; the dispersion/norm
                    # gauges come from the shared cohort statistics
                    cs = cohort_statistics(payloads, weights, accept_f)
                    cohort = {"accept": accept_f, "sel": rreport.sel_mask,
                              "susp": rreport.suspicion,
                              "norm_q": cs.norm_q, "disp": cs.dispersion}
        else:
            with jax.named_scope("fed.aggregate"):
                if self.podscale_armed:
                    # the pod-scale seam (parallel/podscale.py): the
                    # S-invariant grouped hierarchical sum with exactly
                    # ONE cross-shard all-reduce — robust masks, staleness
                    # weights and the DP stage compose on the reduced
                    # estimate unchanged. S == 1 runs the identical add
                    # chains with no collective (the bitwise twin).
                    payload_sum = cohort_hierarchical_sum(
                        payloads, self.mesh, self.client_shards)
                    self._allreduce_bytes = cohort_allreduce_bytes(
                        payloads, k)
                else:
                    payload_sum = jax.tree.map(
                        lambda p: jnp.sum(p, axis=0), payloads)
                if accept is not None:
                    # rejected weight redistributed over survivors;
                    # all-rejected rounds contribute a zero payload (server
                    # holds). Staleness weights (weight_scale) are already
                    # composed into ``weights``, so they renormalize with
                    # it (guards.py).
                    payload_sum = renormalize_accepted(payload_sum, weights,
                                                       accept)
                if self.cohort_stats:
                    accept_f = accept if accept is not None \
                        else jnp.ones((k,))
                    cs = cohort_statistics(payloads, weights, accept_f)
                    cand = accept_f * (weights > 0.0).astype(accept_f.dtype)
                    cohort = {"accept": accept_f, "sel": cand,
                              "susp": cs.suspicion,
                              "norm_q": cs.norm_q, "disp": cs.dispersion}
        with jax.named_scope("fed.aggregate"):
            payload_sum = alg.aggregate_transform(payload_sum)

        with jax.named_scope("fed.guard"):
            # privacy plane, noise half: calibrated Gaussian noise on the
            # RELEASED estimate — sigma = z * clip / cohort_k on the
            # weighted mean (DP-FedAvg server noise), drawn from its own
            # fold of the round key so every other stream is untouched.
            # cohort_k is the round's real width: k_online on the sync
            # planes (over-selection closes on k_online), the commit
            # buffer size m on the async plane (base_params is only
            # threaded by the commit dispatch).
            dp_sigma_t = None
            if self.dp_on:
                dp_k = k if base_params is not None else self.k_online
                dp_sigma = dp_noise_stddev(self.dp_noise_multiplier,
                                           self.dp_clip_norm, dp_k)
                payload_sum = dp_add_noise(payload_sum, rng_round, weights,
                                           dp_sigma, dp_scale)
                dp_sigma_t = (dp_sigma * dp_scale).astype(jnp.float32)

        with jax.named_scope("fed.server_step"):
            new_params, new_opt, new_saux = alg.server_update(
                server.params, server.opt, server.aux, payload_sum,
                online_idx=idx, num_online_eff=num_online_eff,
                client_losses=losses)

        with jax.named_scope("fed.scatter"):
            # aux updates that need the aggregated payload (FedGATE); each
            # client sees its own end-of-round local params, final LR, and
            # EFFECTIVE step count (its epoch-sync budget, not the scan K)
            if self.epoch_sync:
                E = self.cfg.federated.num_epochs_per_comm
                on_budgets = jnp.ceil(on_sizes / B).astype(jnp.int32) * E
            else:
                on_budgets = jnp.full(on_sizes.shape, K, jnp.int32)
            if flt.straggler_rate > 0.0:
                # mirror the in-loop straggler cut so hooks see the steps
                # the client actually took
                on_budgets = jnp.maximum(jnp.ceil(
                    on_budgets.astype(jnp.float32) * plan.budget_scale),
                    1.0).astype(jnp.int32)
            post_aux = jax.vmap(
                lambda d, a, w, p, e, ks: alg.client_post(
                    delta=d, client_aux=a, payload_sum=payload_sum,
                    lr=lr_at(self.schedule, e), local_steps=ks,
                    server_params=server.params, params=p, weight=w)
            )(deltas, new_on_clients.aux, weights, new_on_clients.params,
              new_on_clients.epoch, on_budgets)
            new_on_clients = new_on_clients._replace(
                aux=post_aux,
                # clients leave the round holding the aggregated server model
                # (model_server = deepcopy(model_client), fedavg.py:97)
                params=jax.vmap(lambda _: new_params)(jnp.arange(k)))
            # pod-scale: the broadcast params land cohort-sharded so the
            # [C] scatter below stays a local write per shard group
            new_on_clients = self._shard_cohort(new_on_clients)

            # crash chaos: a crashed client's round never happened on its
            # side — state rolls back to round start, and it reports no
            # metrics (it is not online this round)
            online = jnp.ones((k,))
            if flt.client_drop_rate > 0.0:
                new_on_clients = tree_where(plan.survive, new_on_clients,
                                            on_clients0)
                online = plan.survive
            if self.avail_sync:
                # a mid-round dropout went offline before finishing: its
                # local round never happened (fail-stop, like crash
                # chaos). A deadline miss DID finish training — the client
                # keeps its local state; only its upload was masked at the
                # server. ``online`` counts reporters, so the logged
                # loss/acc are what the server actually observed.
                new_on_clients = tree_where(~avail_drop, new_on_clients,
                                            on_clients0)
                online = online * avail_ok.astype(jnp.float32)

            # scatter online client state back into the full [C] axis
            scatter = lambda full, new: jax.tree.map(
                lambda f, n: f.at[idx].set(n), full, new)
            new_clients = scatter(clients, new_on_clients)

        with jax.named_scope("fed.metrics"):
            # per-client metric leaves: 'perm' keeps the legacy [C]
            # scatter; 'sparse' — the million-client mode — emits the
            # cohort-aligned [k] rows instead. Zero-filling three [C]
            # vectors per round is the last O(C) term on the round's
            # critical path (12 MB/round at C=10^6), and every consumer
            # reduces by sum, which is identical in either layout because
            # offline rows are zeroed; the cohort ids ride ``cohort_idx``
            # when the per-client ledger needs them.
            # lint: disable=FTL005 — participation_mode is a static config
            if self.participation_mode == "sparse":
                mask_full = online
                loss_full = losses * online
                acc_full = accs * online
            else:
                mask_full = jnp.zeros((C,)).at[idx].set(online)
                loss_full = jnp.zeros((C,)).at[idx].set(losses * online)
                acc_full = jnp.zeros((C,)).at[idx].set(accs * online)
            comm_bytes = jnp.asarray(
                tree_bytes(server.params) * k
                * alg.payload_scale(), jnp.float32)
            if flt.client_drop_rate > 0.0 or self.avail_sync:
                # crashed / dropped-out / past-deadline uploads never hit
                # the wire (the server closed the round without them)
                comm_bytes = comm_bytes * jnp.sum(online) / k

        with jax.named_scope("fed.server_step"):
            new_server = ServerState(params=new_params, opt=new_opt,
                                     aux=new_saux, round=server.round + 1,
                                     rng=server.rng)
            # second global phase (DRFA dual update): full data access on
            # the resident plane; on the stream plane the feed carries the
            # host-packed probe batches instead (``probe`` — the same
            # fold_in(rng_round, 99) chain, O(k) device work)
            if probe is not None:
                new_server = alg.post_round_global_feed(
                    new_server, probe, jax.random.fold_in(rng_round, 99))
            else:
                new_server = alg.post_round_global(
                    new_server, data, jax.random.fold_in(rng_round, 99))
        if self.robust_momentum:
            # re-wrap: the updated norm_bound center rides server.aux
            # through checkpoints and the async snapshot ring unchanged
            new_server = new_server._replace(aux={
                "alg": new_server.aux, "norm_bound_m": new_robust_m})
        if self.dp_on:
            # re-wrap: the live noise scale rides server.aux through
            # checkpoints and the snapshot ring unchanged (degrade
            # flips the HOST copy; the program passes it through)
            new_server = new_server._replace(aux={
                "alg": new_server.aux, "dp_noise_scale": dp_scale})
        with jax.named_scope("fed.metrics"):
            # federation-plane cohort fields (telemetry.cohort_stats):
            # per-online-client evidence + heterogeneity gauges. The
            # staleness vector is the sync plane's zeros here; the commit
            # program overwrites it with each job's real commit staleness
            # (parallel/round_program.py:_commit_core).
            cohort_fields = {}
            if cohort is not None:
                cohort_fields = dict(
                    cohort_idx=idx.astype(jnp.int32),
                    cohort_online=online * jnp.ones((k,)),
                    cohort_accept=cohort["accept"],
                    cohort_selected=cohort["sel"],
                    cohort_suspicion=cohort["susp"],
                    cohort_staleness=jnp.zeros((k,)),
                    cohort_norm_q=cohort["norm_q"],
                    cohort_dispersion=cohort["disp"])
            # availability lifecycle counters + the in-jit quorum verdict
            # (all ride RoundMetrics into the loop's one batched fetch).
            # The round ALWAYS commits its renormalized partial cohort —
            # sub-quorum degrades (counted, evented, health intent) or is
            # escalated by the supervisor when avail_quorum_action='abort';
            # the program itself never wedges (all-rejected => the
            # renormalization scale hit 0 and the server held).
            avail_fields = {}
            chaos_dropped = k - jnp.sum(online)
            if self.avail_sync:
                # keep 'dropped' = chaos crashes only; the availability
                # plane reports its own counters
                chaos_dropped = jnp.sum(1.0 - plan.survive)
                n_report = jnp.sum(accept)
                q_flag = jnp.zeros(())
                if flt.avail_quorum_frac > 0.0:
                    quorum = math.ceil(
                        flt.avail_quorum_frac * self.k_online)
                    q_flag = (n_report < quorum).astype(jnp.float32)
                avail_fields = dict(
                    avail_dropped=jnp.sum(avail_drop.astype(jnp.float32)),
                    deadline_missed=jnp.sum(avail_miss.astype(jnp.float32)),
                    quorum_degraded=q_flag)
            # privacy-plane gauges (None = DP off: zero extra outputs)
            dp_fields = {}
            if self.dp_on:
                dp_fields = dict(
                    dp_clipped_frac=dp_clipped_frac.astype(jnp.float32),
                    dp_noise_sigma=dp_sigma_t)
            metrics = RoundMetrics(
                train_loss=loss_full, train_acc=acc_full,
                online_mask=mask_full, comm_bytes=comm_bytes,
                dropped_clients=chaos_dropped,
                straggler_clients=jnp.sum(
                    (plan.budget_scale < 1.0).astype(jnp.float32)),
                rejected_updates=jnp.asarray(rejected, jnp.float32),
                clipped_updates=jnp.asarray(clipped, jnp.float32),
                byzantine_clients=jnp.asarray(byz_count, jnp.float32),
                robust_selected=jnp.asarray(robust_selected, jnp.float32),
                robust_trimmed=jnp.asarray(robust_trimmed, jnp.float32),
                **avail_fields, **cohort_fields, **dp_fields)
        return new_server, new_clients, metrics

    # -- sequential round (cfg.mesh.client_fusion='sequential') -----------
    def _round_core_sequential(self, server: ServerState,
                               clients: ClientState, idx, on_x, on_y,
                               on_sizes, rng_round, rngs, *, data=None,
                               probe=None):
        """``_round_core`` for a model too large to stack: the cohort's
        clients run ONE AFTER ANOTHER, each from the server's
        parameters through its K steps, and each result is folded into
        a running weighted sum (``fed.fold``) and let go of. Three
        parameter-sized trees are live at the peak (the server's, the
        running client's, the sum) where the vmapped round holds
        2k + 1 and the state C more. The cohort draw, row plan, keys,
        weights, local step, payload and server step are the ones the
        vmapped round uses (same hooks, same order), so the two agree
        to the rounding of the sum's order
        (tests/test_sequential_round.py)."""
        cfg, model, alg = self.cfg, self.model, self.algorithm
        K, B, C = self.local_steps, self.batch_size, self.num_clients
        k = idx.shape[0]
        with jax.named_scope("fed.select"):
            num_online_eff = num_online_effective(idx)
            weights = alg.client_weights(server.aux, idx, num_online_eff,
                                         on_sizes)
        with jax.named_scope("fed.gather"):
            on_epoch = jnp.take(clients.epoch, idx)
            on_li = jnp.take(clients.local_index, idx)
        # no buffer where momentum is off (validate_cell refused local
        # momentum): nothing parameter-sized rides the step's carry
        opt0 = optim.init_opt_state((), cfg.optim, lean=True)
        carry0 = model.init_carry(B)
        budget = jnp.asarray(K, jnp.int32)
        # a model with gauges of its own makes them of its loss's
        # parts: asked for here alone, so that every other model's
        # step is as it was
        with_parts = {"with_parts": True} if self.gauge_names else {}

        def one_client(total, member):
            x, y, size, weight, rng_c, epoch0, li0 = member
            nb = jnp.ceil(size / B)

            def step(carry, s):
                params, epoch, li = carry
                lr = lr_at(self.schedule, epoch)
                bx = jax.lax.dynamic_slice_in_dim(x, s * B, B)
                by = jax.lax.dynamic_slice_in_dim(y, s * B, B)
                if self.augment:
                    with jax.named_scope("fed.augment"):
                        aug_parent = jax.random.fold_in(rng_c, 0x7FFFFFFF)
                        bx = augment_image_batch(
                            jax.random.fold_in(aug_parent, s), bx)
                n_params, _, _, _, loss, acc, *parts = alg.local_step(
                    params=params, opt=opt0, client_aux=(),
                    rnn_carry=carry0, server_params=server.params,
                    server_aux=server.aux, bx=bx, by=by, bval_x=None,
                    bval_y=None, lr=lr,
                    rng=jax.random.fold_in(rng_c, s + 1), step_idx=s,
                    local_index=li, step_budget=budget, **with_parts)
                return (n_params, epoch + 1.0 / nb, li + 1), \
                    (loss, acc, *parts)

            with jax.named_scope("fed.local_steps"):
                (params, epoch, li), (losses, accs, *parts) = \
                    jax.lax.scan(step, (server.params, epoch0, li0),
                                 jnp.arange(K))
                with jax.named_scope("fed.fold"):
                    payload, _ = alg.client_payload(
                        delta=tree_sub(server.params, params),
                        client_aux=(), params=params,
                        server_params=server.params,
                        server_aux=server.aux,
                        lr=lr_at(self.schedule, epoch),
                        local_steps=budget, weight=weight,
                        full_loss=None)
                    total = jax.tree.map(jnp.add, total, payload)
            return total, (epoch, li, jnp.mean(losses), jnp.mean(accs),
                           *parts)

        with jax.named_scope("fed.local_steps"):
            with jax.named_scope("fed.fold"):
                zero = tree_zeros_like(server.params)
            payload_sum, (epochs, lis, losses, accs, *parts) = jax.lax.scan(
                one_client, zero,
                (on_x, on_y, on_sizes, weights, rngs, on_epoch, on_li))
        with jax.named_scope("fed.aggregate"):
            payload_sum = alg.aggregate_transform(payload_sum)
        with jax.named_scope("fed.server_step"):
            new_params, new_opt, new_saux = alg.server_update(
                server.params, server.opt, server.aux, payload_sum,
                online_idx=idx, num_online_eff=num_online_eff,
                client_losses=losses)
        with jax.named_scope("fed.scatter"):
            new_clients = clients._replace(
                epoch=clients.epoch.at[idx].set(epochs),
                local_index=clients.local_index.at[idx].set(lis))
        with jax.named_scope("fed.metrics"):
            # lint: disable=FTL005 — participation_mode is static config
            if self.participation_mode == "sparse":
                mask_full, loss_full, acc_full = jnp.ones((k,)), losses, \
                    accs
            else:
                mask_full = jnp.zeros((C,)).at[idx].set(1.0)
                loss_full = jnp.zeros((C,)).at[idx].set(losses)
                acc_full = jnp.zeros((C,)).at[idx].set(accs)
            none = jnp.zeros((), jnp.float32)   # no fault layer here
            metrics = RoundMetrics(
                train_loss=loss_full, train_acc=acc_full,
                online_mask=mask_full,
                comm_bytes=jnp.asarray(
                    tree_bytes(server.params) * k * alg.payload_scale(),
                    jnp.float32),
                dropped_clients=none, straggler_clients=none,
                rejected_updates=none, clipped_updates=none,
                byzantine_clients=none, robust_selected=none,
                robust_trimmed=none)
            if parts:
                # the loss's parts, each stacked [k, K, ...] over the
                # round's clients and steps
                metrics = metrics._replace(
                    model_gauges=model.round_gauges(parts[0]))
        with jax.named_scope("fed.server_step"):
            new_server = ServerState(params=new_params, opt=new_opt,
                                     aux=new_saux, round=server.round + 1,
                                     rng=server.rng)
            if probe is not None:
                new_server = alg.post_round_global_feed(
                    new_server, probe, jax.random.fold_in(rng_round, 99))
            else:
                new_server = alg.post_round_global(
                    new_server, data, jax.random.fold_in(rng_round, 99))
        return new_server, new_clients, metrics

    def _mean_epoch_dev(self, clients) -> jnp.ndarray:
        """Device-side mean training epoch over the REAL clients — the
        one sanctioned reduction over client state: the padded tail
        (pad_client_axis) never advances, so naive means are biased by
        real/padded. Single definition shared by every consumer
        (mean_client_epoch, the round's scalar program, the LocalSGD
        loop)."""
        return self._mean_epoch(clients.epoch)

    def _mean_epoch(self, epoch) -> jnp.ndarray:
        return jnp.mean(epoch[:self.num_clients])

    def mean_client_epoch(self, clients) -> float:
        return float(jax.device_get(self._mean_epoch_dev(clients)))

    # -- preemption stop flag (robustness/preemption.py) ------------------
    def attach_stop_signal(self, fn: Callable[[], bool]) -> None:
        """Register a zero-arg host callable (e.g.
        ``PreemptionHandler.stop_requested``) polled once per round.
        Its value is folded into :meth:`round_host_fetch` as the
        ``"stop"`` entry — on multi-host meshes as a cross-process max
        reduction, so every process agrees on the stop round (a host
        that exits while its peers enter the next round's collective
        would wedge the pod). Riding the existing per-round scalar
        fetch means the agreement costs no extra transfer."""
        self._stop_signal = fn

    def stop_flag_dev(self, local_stop: bool):
        """Scalar = max of ``local_stop`` over all processes (1.0 if
        ANY host wants to stop), on the device. A single process skips
        the collective and the device entirely: its flag is the host
        value, which ``jax.device_get`` passes through."""
        flag = np.float32(1.0 if local_stop else 0.0)
        if jax.process_count() == 1:
            return flag
        sh = client_sharding(self.mesh)
        n = int(self.mesh.devices.size)
        local_rows = sum(1 for d in self.mesh.devices.flat
                         if d.process_index == jax.process_index())
        arr = jax.make_array_from_process_local_data(
            sh, np.full((local_rows,), flag, np.float32), (n,))
        if self._stop_reduce is None:
            self._stop_reduce = jax.jit(
                jnp.max, out_shardings=replicated_sharding(self.mesh))
        return self._stop_reduce(arr)

    @property
    def metrics_width(self) -> int:
        """Leading dim of the per-client RoundMetrics leaves: the full
        [C] in 'perm' mode, the cohort-aligned [k] in 'sparse' mode
        (no per-round [C] materialization — the million-client
        layout). Shape-matching consumers (the supervisor's skipped
        rounds, history stacking) size off this, not num_clients."""
        return self.k_online if self.participation_mode == "sparse" \
            else self.num_clients

    def dp_set_noise_scale(self, server: ServerState,
                           value: float) -> ServerState:
        """Host-side setter for the traced DP noise scale (the budget
        lifecycle's 'degrade': flip to 0.0 and the armed program keeps
        running noise-free). Replaces the aux leaf with a device array
        of the SAME aval and sharding — data changes, the program does
        not, so there is no retrace. Handles the async ring wrapping
        outside the dp wrap."""
        if not self.dp_on:
            raise ValueError(
                "dp_set_noise_scale on a trainer without DP armed "
                "(fault.dp_noise_multiplier == 0)")
        aux = server.aux
        ring = None
        if isinstance(aux, dict) and "ring" in aux:
            ring, aux = aux["ring"], aux["alg"]
        leaf = aux["dp_noise_scale"]
        new_leaf = jax.device_put(
            jnp.asarray(value, jnp.float32), leaf.sharding)
        aux = dict(aux, dp_noise_scale=new_leaf)
        if ring is not None:
            aux = {"alg": aux, "ring": ring}
        return server._replace(aux=aux)

    def _round_scalars(self, schedule: LRSchedule, epoch,
                       metrics: RoundMetrics):
        """Body of the round's scalar program
        (``self.scalars_trace_name``; under ``jax.jit`` only, which
        specialises on the leaves ``metrics`` holds):
        ``_COMPUTED_SCALARS`` then ``_scalar_leaves``' values, as ONE
        float32 array, so one copy brings them to the host. Nothing is
        cast: a leaf of another dtype is refused when the program is
        traced."""
        leaves = _scalar_leaves(metrics, self.gauge_names)
        if self.padded_clients != self.num_clients:
            # a padded axis is cut on a replicated copy and summed in
            # index order on every device: what eager _mean_epoch_dev
            # does (a slice whose length does not divide over the mesh
            # comes back replicated); summed shard by shard, the mean
            # differs from that in the last place
            epoch = jax.lax.with_sharding_constraint(
                epoch, replicated_sharding(self.mesh))
        mean_epoch = self._mean_epoch(epoch)
        values = (mean_epoch,
                  # the logged LR is a jnp computation over the
                  # schedule's arrays
                  lr_at(schedule, mean_epoch),
                  jnp.sum(metrics.online_mask),
                  jnp.sum(metrics.train_loss),
                  jnp.sum(metrics.train_acc), *leaves.values())
        for key, v in zip(_COMPUTED_SCALARS + tuple(leaves), values):
            if jnp.result_type(v) != jnp.float32 or jnp.ndim(v):
                raise TypeError(
                    f"round scalar {key!r} is {jnp.result_type(v)}"
                    f"{list(jnp.shape(v))}, not a float32 scalar: "
                    "the packed fetch casts nothing")
        return jnp.stack(values)

    def round_host_fetch(self, clients, metrics, extra=None) -> tuple:
        """``(scalars, extra on the host)``: everything the host round
        loop logs, as Python floats by key, and whatever further
        device values the caller wants with them (the ledger's cohort
        vectors, the supervisor's finite flag), in ONE batched
        ``device_get``. The scalars are ONE compiled program's ONE
        array: the program is handed ``clients.epoch`` and the
        metrics' scalar leaves, never the client-state tree (a
        program's dispatch costs by the leaves it is handed). With a
        stop signal attached (:meth:`attach_stop_signal`) the dict
        also carries the SPMD-agreed ``"stop"`` flag: a host value on
        one process, the cross-process max in the same fetch on
        several."""
        packed = self._scalars_jit(
            self._schedule_dev, clients.epoch,
            metrics._replace(**_NO_COHORT_VECTORS))
        stop = None if self._stop_signal is None else \
            self.stop_flag_dev(bool(self._stop_signal()))
        values, stop, extra = jax.device_get((packed, stop, extra))
        scalars = dict(zip(
            _COMPUTED_SCALARS
            + tuple(_scalar_leaves(metrics, self.gauge_names)),
            values.tolist()))
        if stop is not None:
            scalars["stop"] = float(stop)
        return scalars, extra

    def cohort_fetch_dev(self, metrics) -> Optional[dict]:
        """Device-side per-client cohort vectors for the ledger
        (telemetry/ledger.py): online ids, survive/accept/selection
        masks, the robust rule's suspicion, per-job staleness, and the
        [5] update-norm quantiles. None when ``cohort_stats`` is off.
        The CLI loop hands this dict to :meth:`round_host_fetch` as its
        ``extra``, so the per-round device-sync count
        stays at the one fetch (docs/observability.md "Federation
        plane")."""
        if metrics.cohort_idx is None:
            return None
        return {
            "idx": metrics.cohort_idx,
            "online": metrics.cohort_online,
            "accept": metrics.cohort_accept,
            "selected": metrics.cohort_selected,
            "suspicion": metrics.cohort_suspicion,
            "staleness": metrics.cohort_staleness,
            "norm_q": metrics.cohort_norm_q,
        }

    def round_host_scalars(self, clients, metrics) -> dict:
        """Everything the host round loop logs, fetched in ONE batched
        ``device_get`` — the per-round alternative to a pile of
        ``float(...)`` calls that each block on a separate transfer
        (fedtorch_tpu.lint FTL001; docs/static_analysis.md)."""
        return self.round_host_fetch(clients, metrics)[0]

    # -- telemetry gauges (fedtorch_tpu.telemetry) ------------------------
    def stream_stats(self) -> Optional[dict]:
        """Stream-plane producer gauges (prefetch depth, producer
        gather/H2D wall, consumer wait) — None on the device plane or
        before the first streamed round. Host counters only: reading
        them costs no device sync."""
        s = getattr(self, "_stream", None)
        return s.stats() if s is not None else None

    def telemetry_gauges(self) -> dict:
        """Host-side subsystem gauges riding the telemetry round row
        (docs/observability.md "Metric catalog") — values that used to
        die in process memory. Strictly host counters: the row stays
        zero-extra-device-syncs by construction. Subclasses extend
        (the async plane adds its scheduler counters)."""
        out = {}
        if self.tokens_per_round:
            out["tokens_trained"] = float(self.tokens_per_round)
            # what the model knows of the step as it is traced here
            # (models/common.py ``is_token_model``)
            trace_gauges = getattr(self.model, "trace_gauges", None)
            if trace_gauges is not None:
                out.update(trace_gauges(self.batch_size, self.row_tokens))
        ss = self.stream_stats()
        if ss is not None:
            out.update(ss)
        if self.data_plane == "stream":
            out["stream_rebuilds"] = float(self._stream_rebuilds)
        if self.podscale_armed:
            # pod-scale gauges (docs/performance.md "Pod-scale round
            # programs"): the shard count and the static [G, P] bytes
            # the seam's one all-reduce moves per round (stashed at
            # trace time; absent until the first round traces)
            out["client_shards"] = float(self.client_shards)
            if self._allreduce_bytes is not None:
                out["cohort_allreduce_bytes"] = float(
                    self._allreduce_bytes)
        return out

    def staleness_histogram(self) -> Optional[dict]:
        """{commits-stale: count} over committed updates — async
        commit plane only (None here)."""
        return None

    # -- streaming feed plumbing (data_plane='stream') --------------------
    def _next_stream_feed(self, server, window: int = 0) -> RoundFeed:
        """Pop the next host-packed feed (``window == 0``, run_round)
        or ``[window, ...]`` stacked feed window (the scanned streamed
        program, run_rounds), (re)starting the producer from the LIVE
        device state on first use, after :meth:`invalidate_stream`, or
        when the dispatch granularity changes (feeds are strictly
        sequential per producer, so a window switch re-syncs). The
        (rng, round) fetch is one batched ``device_get`` paid only at
        (re)start — steady-state dispatches consume prefetched feeds
        without touching the device stream, and the producer stays
        >= 1 window ahead."""
        if self._stream is not None and self._stream.window != window:
            self.invalidate_stream()
        if self._stream is None:
            key_data, round0 = jax.device_get(
                (jax.random.key_data(server.rng), server.round))
            # place_fn must NOT close over self: the producer thread
            # holds it, and a reference back to the trainer would keep
            # a dropped trainer (and its jit caches) alive forever
            mesh = self.mesh
            alg = self.algorithm
            if self.podscale_armed:
                # pod-scale stream plane: this host's producer packs
                # ONLY its shard's cohort rows and the placer
                # assembles the cohort-sharded global feed
                place = podscale_feed_placer(mesh, self.k_dispatch)
                cohort_rows = local_cohort_rows(
                    mesh, self.k_dispatch, self.client_shards)
            else:
                place = lambda t: replicate(t, mesh)
                cohort_rows = None
            self._stream = StreamFeedProducer(
                self.host_store, key_data=key_data,
                key_impl=jax.random.key_impl(server.rng),
                start_round=int(round0), num_clients=self.num_clients,
                k_online=self.k_dispatch, local_steps=self.local_steps,
                batch_size=self.batch_size, window=window,
                participation_mode=self.participation_mode,
                probe_fn=(alg.host_probe_fn(self.host_store.sizes)
                          if alg.needs_post_probe else None),
                feed_layout=self.gather_mode,
                cohort_rows=cohort_rows, place_fn=place)
            # leak guard: a trainer dropped WITHOUT invalidate_stream
            # must not orphan the producer thread (it would pin the
            # host store + the placed feeds for the process lifetime)
            self._stream_finalizer = weakref.finalize(
                self, StreamFeedProducer.close, self._stream)
        return self._stream.next_feed()

    def invalidate_stream(self) -> None:
        """Drop the feed producer and every prefetched round. Call
        whenever host-visible training state stops matching the
        producer's replay — supervisor rollback/reseed, checkpoint
        resume into an existing trainer, preemption drain, end of run.
        The next streamed round re-syncs from the live device state.
        No-op on the device data plane (and before the first streamed
        round)."""
        if getattr(self, "_stream", None) is not None:
            if self._stream_finalizer is not None:
                self._stream_finalizer.detach()
                self._stream_finalizer = None
            self._stream.close()
            self._stream = None

    def _pop_stream_with_rebuild(self, pop: Callable):
        """Self-healing feed pop (docs/robustness.md "Host plane"):
        when the producer fails — its thread died on an exhausted
        gather retry, wedged past ``timeout_s``, or desynced — tear it
        down and REBUILD it through the :meth:`invalidate_stream`
        resync instead of aborting the run. The rebuilt producer
        replays the identical deterministic index schedule from the
        live device (rng, round), so recovery is exact (bitwise), not
        approximate. Bounded by ``fault.host_retry_max`` rebuilds per
        pop; exhaustion raises a seam-named :class:`HostSeamError`
        the supervisor counts per seam. ``pop`` must (re)construct the
        producer from live state when none exists — both planes'
        pops do."""
        limit = self.cfg.fault.host_retry_max
        for attempt in range(limit + 1):
            try:
                return pop()
            except Exception as e:
                self.invalidate_stream()
                if attempt >= limit:
                    raise host_recovery.HostSeamError(
                        "stream.producer",
                        f"stream feed producer failed {limit + 1} "
                        f"consecutive pops; last error: {e!r}") from e
                self._stream_rebuilds += 1
                host_recovery.get_active().note_retry("stream.producer")
                telemetry.event("stream.producer_rebuilt",
                                attempt=attempt + 1, error=repr(e))

    # -- host-side round loop ---------------------------------------------
    def run_round(self, server, clients):
        """One communication round. STREAM-PLANE CONTRACT: each call
        consumes the producer's next sequential feed, so calls must
        advance the state monotonically (the returned server carries
        round+1). Replaying a round on saved/copied state — legal and
        idempotent on the device plane — requires
        :meth:`invalidate_stream` first so the producer re-syncs to
        the replayed (rng, round); the supervisor's retry path and the
        CLI resume path already do this."""
        if self.data_plane == "stream":
            feed = self._pop_stream_with_rebuild(
                lambda: self._next_stream_feed(server))
            return self._round_stream_jit(server, clients, feed)
        return self._round_jit(server, clients, self.data, self.val_data)

    def run_rounds(self, server, clients, num_rounds: int):
        """``num_rounds`` communication rounds in ONE device call: the
        round program scanned with ``lax.scan``, so the host dispatches
        once instead of once per round (no per-round Python/dispatch
        gap on the device timeline — the bench path). Metrics come back
        with a leading [num_rounds] axis. Per-round trajectories equal
        ``num_rounds`` calls of :meth:`run_round` (bitwise on XLA CPU —
        pinned per cell in tests/test_round_builder.py; the scan body
        is a separate XLA compilation, so other backends may
        reassociate float math at ulp level). One jitted driver is
        cached per distinct (source, ``num_rounds``).

        Both data sources scan. On the resident source the scan closes
        over the full data pytree in HBM (the seed fast path). On the
        feed source this is the SCANNED STREAMED program: the producer
        packs an ``[num_rounds, k, K*B, ...]`` feed WINDOW — window
        r+1 built while the device scans window r — so the stream
        plane gets the dispatch lever and the producer overlap has a
        whole window of compute to hide under. Device feed residency
        grows from O((depth+1)*k*K*B) to O((depth+1)*R*k*K*B).
        Switching dispatch granularity mid-run (run_round <->
        run_rounds, or a different ``num_rounds``) re-syncs the
        producer from live device state — one batched fetch, exact
        replay. The async commit plane refuses here with the
        cell-named ValueError (commits are host-scheduled events)."""
        if num_rounds < 1:
            # refuse BEFORE any feed is consumed: a zero-length scan
            # traces to an obscure shape error, and on the stream
            # plane it would first pop (and lose) a real feed —
            # desyncing the producer from the device round
            raise ValueError(
                f"run_rounds needs num_rounds >= 1, got {num_rounds}")
        key = (self.programs.source, num_rounds)
        if key not in self._rounds_jit:
            # build() validates the scan cell — the one error site;
            # the async plane's refusal fires here, at call time
            fn = self.programs.build("scan", scan_length=num_rounds)
            suffix = "" if self.programs.source == "resident" \
                else "_stream"
            self._rounds_jit[key] = jax.jit(
                instrument_trace(
                    f"federated.rounds{suffix}[{self.algorithm.name}]"
                    f"x{num_rounds}", fn),
                donate_argnums=(0, 1))
        if self.data_plane == "stream":
            window = self._pop_stream_with_rebuild(
                lambda: self._next_stream_feed(server,
                                               window=num_rounds))
            return self._rounds_jit[key](server, clients, window)
        return self._rounds_jit[key](server, clients, self.data,
                                     self.val_data)

    # -- compiled-program cost capture (telemetry.costs) ------------------
    def _feed_struct(self, k: Optional[int] = None) -> RoundFeed:
        """Abstract (shape/dtype/sharding) twin of one packed feed —
        lets cost capture lower the streamed program without consuming
        a real prefetched feed from the producer."""
        st = self.host_store
        k = self.k_dispatch if k is None else k
        # 'batch' layout packs the round's K*B touched rows; 'shard'
        # (the full-loss feed plan) packs whole padded shards
        KB = st.n_max if self.gather_mode == "shard" \
            else self.local_steps * self.batch_size
        sh = replicated_sharding(self.mesh)
        # pod-scale: the big cohort tensors go up cohort-sharded
        # (mirroring podscale_feed_placer exactly — the lowered twin
        # must see the live program's input layout)
        csh = cohort_sharding(self.mesh) if self.podscale_armed else sh
        sds = lambda shape, dt, s=sh: jax.ShapeDtypeStruct(
            shape, dt, sharding=s)
        fx, fy = st.feat("x"), st.feat("y")
        dx, dy = st.dtype("x"), st.dtype("y")
        probe = {}
        if self.algorithm.needs_post_probe:
            k2 = self.algorithm.k_online
            probe = dict(
                probe_idx=sds((k2,), jnp.int32),
                probe_x=sds((k2, self.batch_size) + fx, dx),
                probe_y=sds((k2, self.batch_size) + fy, dy))
        return RoundFeed(
            idx=sds((k,), jnp.int32), sizes=sds((k,), st.sizes.dtype),
            x=sds((k, KB) + fx, dx, csh),
            y=sds((k, KB) + fy, dy, csh),
            pre_x=sds((k, self.batch_size) + fx, dx, csh),
            pre_y=sds((k, self.batch_size) + fy, dy, csh), **probe)

    def _window_struct(self, num_rounds: int) -> RoundFeed:
        """Abstract twin of a packed ``[R, ...]`` feed window — the
        scanned streamed program's data input (:meth:`_feed_struct`
        with a leading window axis; cohort-sharded fields keep the
        shard axis on the COHORT dim, not the new window dim)."""
        def widen(s):
            sh = s.sharding
            if isinstance(sh, NamedSharding) and tuple(sh.spec):
                sh = NamedSharding(sh.mesh,
                                   PartitionSpec(None, *sh.spec))
            return jax.ShapeDtypeStruct((num_rounds,) + s.shape,
                                        s.dtype, sharding=sh)
        return jax.tree.map(widen, self._feed_struct())

    def lowered_cost_programs(self, server, clients,
                              num_scan_rounds: int = 0):
        """``({name: jax.stages.Lowered}, primary_name)`` for this
        trainer's jitted programs, AOT-lowered from UNINSTRUMENTED
        twins of the same functions with the same donation — so the
        HLO is byte-identical to the live programs' (pinned in
        tests/test_device_observability.py), the recompilation
        sentinel sees zero extra trace events, and the live jit caches
        are untouched. ``primary`` names the per-round program whose
        FLOPs feed the measured-MFU gauge. ``num_scan_rounds > 0``
        additionally lowers the ``run_rounds`` scan-of-R driver for
        the active data source — the composed builder programs
        (resident scan AND the scanned streamed program) are both
        cost-capturable, against an abstract feed-window struct on the
        feed source so no prefetched feed is consumed.

        Lowering alone executes no device work; compiling the twins
        (telemetry.costs.lowered_cost) re-uses the persistent XLA
        compilation cache the live program already warmed."""
        programs = {}
        if self.data_plane == "stream":
            primary = "round_stream"
            programs[primary] = jax.jit(
                self.round_stream_fn, donate_argnums=(0, 1)).lower(
                server, clients, self._feed_struct())
            if num_scan_rounds > 0:
                programs[f"rounds_stream_scan[{num_scan_rounds}]"] = \
                    jax.jit(
                        self.programs.build(
                            "scan", scan_length=num_scan_rounds),
                        donate_argnums=(0, 1)).lower(
                        server, clients,
                        self._window_struct(num_scan_rounds))
        else:
            primary = "round"
            programs[primary] = jax.jit(
                self.round_fn, donate_argnums=(0, 1)).lower(
                server, clients, self.data, self.val_data)
            if num_scan_rounds > 0:
                programs[f"rounds_scan[{num_scan_rounds}]"] = jax.jit(
                    self.programs.build(
                        "scan", scan_length=num_scan_rounds),
                    donate_argnums=(0, 1)).lower(
                    server, clients, self.data, self.val_data)
        return programs, primary

    def fit(self, rng: jax.Array, num_rounds: Optional[int] = None,
            callback=None):
        """The num_comms round loop (federated/main.py:56-211)."""
        server, clients = self.init_state(rng)
        rounds = num_rounds if num_rounds is not None \
            else self.cfg.federated.num_comms
        history = []
        for _ in range(rounds):
            server, clients, metrics = self.run_round(server, clients)
            if callback is not None:
                callback(server, clients, metrics)
            history.append(metrics)
        return server, clients, history
