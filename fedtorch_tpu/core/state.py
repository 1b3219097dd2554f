"""Training-state pytrees.

The reference holds mutable per-process objects (``Client`` owns model,
model_server, optimizer, aux models — nodes/nodes.py:43-112 — and scribbles
runtime counters into ``args``, SURVEY.md §5.6). Here all of that is two
immutable pytrees:

* :class:`ClientState` — every array has a leading ``[num_clients]`` axis;
  ``vmap`` over it is the reference's centered mode, sharding it over the
  mesh is distributed mode (SURVEY.md §7).
* :class:`ServerState` — replicated across devices; includes the PRNG key
  and round counter, so a checkpoint of (ServerState, ClientState) resumes
  the *exact* run — including client aux state the reference loses on
  resume (SURVEY.md §5.4).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class ClientState(NamedTuple):
    """Per-client state; every leaf has leading axis [C]."""
    params: Any        # working model copy (nodes.py:52 `model`)
    opt: Any           # optimizer state incl. dual momentum buffers
    aux: Any           # algorithm aux (gen_aux_models, nodes.py:87-112)
    epoch: jnp.ndarray        # [C] float — fractional local epoch
    local_index: jnp.ndarray  # [C] int — local step counter


class ServerState(NamedTuple):
    params: Any        # aggregated model (nodes.py `model_server`)
    opt: Any           # server optimizer state (out-momentum buffers)
    aux: Any           # server aux (control variates, fedadam_v, lambda)
    round: jnp.ndarray        # scalar int
    rng: jax.Array            # threaded PRNG key


class RoundMetrics(NamedTuple):
    """What the reference logs per round (logs/logging.py:83-117), plus
    the robustness counters (docs/robustness.md): a client that crashed
    mid-round is removed from ``online_mask`` (it contributed nothing),
    and the fault scalars record what the chaos layer and the update
    guards did this round. All are 0 when faults/guards are off.

    The three per-client leaves are [C] under the legacy 'perm'
    participation mode and cohort-aligned [k] under 'sparse' (the
    million-client mode never materializes a [C] vector per round —
    docs/performance.md "The million-client store"); every shipped
    consumer reduces them by sum, which is layout-invariant because
    offline rows are zeroed. ``FederatedTrainer.metrics_width`` names
    the active width for shape-matching consumers."""
    train_loss: jnp.ndarray   # [C]|[k] mean local loss (masked)
    train_acc: jnp.ndarray    # [C]|[k] mean local top-1 (masked)
    online_mask: jnp.ndarray  # [C]|[k]
    comm_bytes: jnp.ndarray   # scalar — payload volume this round
    dropped_clients: jnp.ndarray = 0.0    # scalar — chaos crashes
    straggler_clients: jnp.ndarray = 0.0  # scalar — step-budget cuts
    # (async plane: delayed dispatches folded into this commit)
    rejected_updates: jnp.ndarray = 0.0   # scalar — guard rejections
    clipped_updates: jnp.ndarray = 0.0    # scalar — guard norm clips
    # async commit plane only: mean commit-version staleness of the
    # buffered updates this commit consumed (0 on the sync planes)
    staleness_mean: jnp.ndarray = 0.0     # scalar
    # byzantine adversary + robust aggregation (robustness/chaos.py,
    # robustness/aggregators.py): adversarial uploads injected this
    # round, updates the robust rule aggregated, and updates it
    # excluded/clipped beyond the guards. All 0 when off.
    byzantine_clients: jnp.ndarray = 0.0  # scalar — crafted uploads
    robust_selected: jnp.ndarray = 0.0    # scalar — updates aggregated
    robust_trimmed: jnp.ndarray = 0.0     # scalar — excluded/clipped
    # deployment-realism round lifecycle (robustness/availability.py,
    # docs/robustness.md "Deployment realism"): mid-round dropouts,
    # survivors that reported after the round closed on its first
    # k_online arrivals, and whether the reporting cohort fell below
    # the configured quorum (the round still commits its renormalized
    # partial cohort — degraded, never wedged). All 0 when the
    # availability plane is disarmed.
    avail_dropped: jnp.ndarray = 0.0      # scalar — mid-round dropouts
    deadline_missed: jnp.ndarray = 0.0    # scalar — late survivors
    quorum_degraded: jnp.ndarray = 0.0    # scalar {0,1} — sub-quorum
    # federation-plane cohort statistics (telemetry.cohort_stats —
    # docs/observability.md "Federation plane"). None (the default)
    # contributes ZERO pytree leaves, so with the gauge off the round
    # program's outputs — and its HLO — are byte-identical to the
    # pre-cohort engine. When on, all are per-ONLINE-client [k]
    # (async: per buffered job [m]) except the [5] norm quantiles and
    # the scalar dispersion; they ride the loop's one batched fetch
    # into the per-client ledger (telemetry/ledger.py).
    cohort_idx: Any = None         # [k] int32 online client ids
    cohort_online: Any = None      # [k] {0,1} survived the round
    cohort_accept: Any = None      # [k] {0,1} chaos+guard candidate
    cohort_selected: Any = None    # [k] {0,1} the rule aggregated it
    cohort_suspicion: Any = None   # [k] robust-rule suspicion score
    cohort_staleness: Any = None   # [k] commit staleness (0 on sync)
    cohort_norm_q: Any = None      # [5] update-norm quantiles
    cohort_dispersion: Any = None  # scalar 1 - mean cos(u_i, mean)
    # privacy plane (robustness/privacy.py; docs/robustness.md
    # "Privacy plane"). None (default) contributes ZERO pytree leaves
    # — DP off keeps the round program HLO byte-identical.
    dp_clipped_frac: Any = None    # scalar [0,1] — accepted clients clipped
    dp_noise_sigma: Any = None     # scalar — applied noise stddev
    #                                (sigma * noise_scale; 0 after degrade)
    # a token model's own gauges (models/common.py ``is_token_model``):
    # the tuple of float32 scalars its ``round_gauges`` makes of the
    # loss parts, in its ``gauge_names``' order, from the sequential
    # execution. None for every other model and execution: zero
    # leaves, the round program as it was.
    model_gauges: Any = None


def tree_where(pred, on_true, on_false):
    """Per-client select: ``pred`` is [C], leaves have leading axis C."""
    def sel(a, b):
        shape = (-1,) + (1,) * (a.ndim - 1)
        return jnp.where(pred.reshape(shape).astype(bool), a, b)
    return jax.tree.map(sel, on_true, on_false)


def tree_weighted_sum(tree, weights):
    """sum_i w_i * leaf[i] over the leading client axis."""
    def ws(a):
        shape = (-1,) + (1,) * (a.ndim - 1)
        return jnp.sum(a * weights.reshape(shape).astype(a.dtype), axis=0)
    return jax.tree.map(ws, tree)


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_scale(tree, s):
    return jax.tree.map(lambda x: x * s, tree)


def tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def tree_broadcast_clients(tree, num_clients: int):
    """Tile a replicated pytree to a leading [C] axis."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_clients,) + x.shape), tree)


def tree_bytes(tree) -> int:
    """Static payload size in bytes (for comm accounting, SURVEY.md §5.1)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
