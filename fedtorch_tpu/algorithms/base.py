"""Federated algorithm interface.

The reference couples each algorithm's logic across three places: aux-state
construction (nodes/nodes.py:87-112 ``gen_aux_models``), in-loop gradient
corrections (comms/trainings/federated/main.py:116-129), and an aggregation
function (comms/algorithms/federated/*). Here an algorithm is one object
with pure-function hooks; the engine (parallel/federated.py) calls them

* under ``vmap`` over the client axis (aux init, grad transform, payload),
* replicated for the server update.

All hooks must be jit-traceable: static shapes, no Python control flow on
traced values.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from fedtorch_tpu.config import ExperimentConfig
from fedtorch_tpu.core import optim
from fedtorch_tpu.core.losses import accuracy  # noqa: F401 (hook use)
from fedtorch_tpu.core.state import tree_scale
from fedtorch_tpu.models.common import is_token_model


def num_online_effective(online_idx: jnp.ndarray) -> jnp.ndarray:
    """The reference's weighting denominator (fedavg.py:18-27): |online|
    when client 0 is online, |online|+1 otherwise (the MPI server shares
    rank 0 with a client). Shared by the engine and DRFA's second
    sampling phase."""
    k = online_idx.shape[0]
    has0 = jnp.any(online_idx == 0).astype(jnp.float32)
    return k + (1.0 - has0)


class FedAlgorithm:
    """Base = FedAvg behavior; subclasses override hooks."""

    name = "fedavg"
    # engine computes each online client's full-data loss on the incoming
    # server model when set (qFFL, centered/main.py:62-72)
    needs_full_loss = False

    # set when the algorithm consumes a per-step validation batch
    # (PerFedAvg's MAML outer step; requires cfg.federated.personal)
    needs_val_batch = False

    # True when the host RoundSchedule can replay this algorithm's
    # participation draw bit-exactly (the stream plane's precondition:
    # the feed packer must know the cohort before the round runs). The
    # base default samples uniformly from the round key alone, which
    # the schedule replays; an override that reads DEVICE state the
    # host cannot see (DRFA's lambda-distributed sampling) must leave
    # this False — the cell validator refuses the feed source then.
    # Subclasses overriding ``participation`` with a replayable draw
    # flip this True (or make it a property over their config).
    participation_replayable = True

    # True when the algorithm's ``post_round_global`` phase can run on
    # the stream plane from a host-packed probe (``host_probe_fn`` +
    # ``post_round_global_feed`` below — DRFA's dual update). False
    # with an overridden ``post_round_global`` means the feed source
    # is refused (the phase needs full data access).
    needs_post_probe = False

    def __init__(self, cfg: ExperimentConfig):
        self.cfg = cfg
        self.model = None
        self.criterion = None
        # set by the engine before tracing (static round length / static
        # online-client count / mesh size)
        self.local_steps_per_round = max(cfg.train.local_step, 1)
        # devices the client axis is sharded over; wire-format kernels
        # without a partitioning rule (pallas) must stay off when > 1
        self.mesh_devices = 1
        self.k_online = max(
            int(cfg.federated.online_client_rate
                * cfg.federated.num_clients), 1)

    def setup(self, data) -> None:
        """One-time hook with the ClientData (sample-size weighting)."""

    def bind(self, model, criterion) -> None:
        """Engine provides the model/criterion so algorithm hooks can run
        forwards/backwards of their own (personal models)."""
        self.model = model
        self.criterion = criterion

    # -- state ---------------------------------------------------------
    def init_client_aux(self, params) -> Any:
        """Per-client aux pytree (called under vmap). () = none."""
        return ()

    def init_server_aux(self, params, num_clients: int) -> Any:
        return ()

    # -- local loop hooks (per client, inside the scan) ------------------
    def forward_reset(self, params, bx, *, train: bool = False, rng=None):
        """Forward pass with a FRESH zero hidden carry for recurrent
        models — the policy for every AUXILIARY forward (personal models,
        MAML outer steps, DRFA's kth-model loss probe). The reference
        re-inits hidden per round for its main loop
        (centered/main.py:96-97) and starts auxiliary inferences fresh
        (centered/drfa.py:242); only the engine's main local loop threads
        a carry across steps."""
        model = self.model
        if model.is_recurrent:
            logits, _ = model.apply(
                params, bx, train=train, rng=rng,
                carry=model.init_carry(bx.shape[0]))
            return logits
        return model.apply(params, bx, train=train, rng=rng)

    def extra_loss(self, params, server_params, client_aux) -> jnp.ndarray:
        """Added to the batch loss (FedProx's proximal term)."""
        return jnp.asarray(0.0)

    def transform_grads(self, grads, *, params, server_params, client_aux,
                        server_aux, lr):
        """Gradient correction before the optimizer step
        (fedgate main.py:116-119, scaffold main.py:120-122)."""
        return grads

    def participation(self, rng, num_clients: int, k: int, round_idx,
                      server_aux):
        """Override to control online-client sampling; return a [k] index
        array or None for the engine's default uniform sampling
        (misc.py:10-19). DRFA samples from the lambda distribution
        (misc.py:30-37)."""
        return None

    def post_round_global(self, server, data, rng):
        """Optional second phase after aggregation with full data access
        (DRFA's kth-model loss collection + dual update,
        drfa.py:215-249). Returns the updated ServerState."""
        return server

    def host_probe_fn(self, sizes):
        """Host replica of the ``post_round_global`` phase's DATA
        plan, for the stream plane (``needs_post_probe``): return a
        closure ``probe(rng_round) -> (probe_idx, probe_rows)`` that
        replays — on the CPU backend, bit-exactly — which clients' and
        which storage rows the post phase will consume, from the same
        round key chain the device phase folds. The feed packer
        gathers those rows into ``RoundFeed.probe_*``. None (default)
        = no probe."""
        return None

    def post_round_global_feed(self, server, probe, rng):
        """The ``post_round_global`` twin for the stream plane: same
        math, but over the pre-gathered probe batches (a ``RoundFeed``
        with ``probe_idx``/``probe_x``/``probe_y``) instead of the
        full data pytree — O(k) device work, no [C, n_max, ...]
        input. Must be bitwise-identical to ``post_round_global``
        given the probe ``host_probe_fn`` planned. Returns the updated
        ServerState."""
        return server

    def pre_round(self, on_aux, *, server, x, y, sizes, lr, rng):
        """Once per round, on the gathered [k] online-client aux, OUTSIDE
        the vmapped local loop — the place for cross-client work like
        APFL's globally-averaged adaptive alpha (apfl.py:119-123).
        ``x``/``y``: each online client's first batch (first B
        storage-order rows, identical in every gather mode);
        ``lr``: [k] scheduled LR at each online client's current epoch."""
        return on_aux

    def local_step(self, *, params, opt, client_aux, rnn_carry,
                   server_params, server_aux, bx, by, bval_x, bval_y, lr,
                   rng, step_idx, local_index, step_budget=None,
                   with_parts: bool = False):
        """One local training step (the hot loop body,
        federated/main.py:83-155). The base implements the standard
        inference -> backward -> per-algorithm grad correction ->
        dual-mode SGD step; personalized algorithms override or extend.

        ``step_budget`` is the client's EFFECTIVE step count this round
        (its epoch-sync budget; == the scan length in local-step mode):
        steps at index >= step_budget run but are masked out by the
        engine, so step-indexed logic (sync pulls, snapshots) must
        anchor on the budget, not the scan length.

        Returns (params, opt, client_aux, rnn_carry, loss, acc) and,
        ``with_parts``, seventh the parts a token model's loss reports
        beside its value (``token_loss_parts``: a dict of float32
        arrays, empty for a single-pass model). An override that does
        not know the option refuses it by its signature."""
        model, criterion, cfg = self.model, self.criterion, self.cfg

        if is_token_model(model):
            # a token model makes its target from the batch itself (the
            # next token); a row's label takes no part in the loss
            def token_loss_fn(p):
                loss, acc, parts = model.token_loss_parts(
                    p, bx, train=True, rng=rng)
                return loss + self.extra_loss(p, server_params,
                                              client_aux), (acc, parts)

            with jax.named_scope("fed.forward_backward"):
                (loss, (acc, parts)), grads = jax.value_and_grad(
                    token_loss_fn, has_aux=True)(params)
                grads = self.transform_grads(
                    grads, params=params, server_params=server_params,
                    client_aux=client_aux, server_aux=server_aux, lr=lr)
            with jax.named_scope("fed.opt_step"):
                params, opt = optim.local_step(params, grads, opt, lr,
                                               cfg.optim)
            out = (params, opt, client_aux, rnn_carry, loss, acc)
            return out + (parts,) if with_parts else out

        moe_w = cfg.model.moe_aux_weight

        def loss_fn(p):
            aux_reg = jnp.asarray(0.0)
            if model.is_recurrent:
                logits, new_rnn = model.apply(p, bx, train=True, rng=rng,
                                              carry=rnn_carry)
            else:
                new_rnn = rnn_carry
                if model.has_aux_loss and moe_w > 0:
                    logits, aux = model.apply_with_aux(
                        p, bx, train=True, rng=rng)
                    aux_reg = moe_w * aux
                else:
                    logits = model.apply(p, bx, train=True, rng=rng)
            loss = criterion(logits, by) + aux_reg
            loss = loss + self.extra_loss(p, server_params, client_aux)
            return loss, (logits, new_rnn)

        with jax.named_scope("fed.forward_backward"):
            (loss, (logits, new_rnn)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = self.transform_grads(
                grads, params=params, server_params=server_params,
                client_aux=client_aux, server_aux=server_aux, lr=lr)
            if model.has_noise_param:
                # robust archs: gradient ASCENT on the adversarial input
                # noise (federated/main.py:131-141)
                grads = dict(grads)
                grads["noise"] = -grads["noise"]
        with jax.named_scope("fed.opt_step"):
            params, opt = optim.local_step(params, grads, opt, lr,
                                           cfg.optim)
        acc = jnp.asarray(0.0) if model.is_regression \
            else accuracy(logits, by)
        out = (params, opt, client_aux, new_rnn, loss, acc)
        return out + ({},) if with_parts else out

    # -- aggregation -----------------------------------------------------
    def client_weights(self, server_aux, online_idx, num_online_eff,
                       sizes) -> jnp.ndarray:
        """Aggregation weights [k] for the gathered online clients.

        ``num_online_eff`` is the reference denominator (fedavg.py:18-27):
        |online| when client 0 is online, |online|+1 otherwise (the MPI
        server shares rank 0 with a client). Default: uniform
        1/num_online_eff; AFL/DRFA override with lambda weights."""
        k = online_idx.shape[0]
        return jnp.full((k,), 1.0) / num_online_eff

    def client_payload(self, *, delta, client_aux, params, server_params,
                       server_aux, lr, local_steps, weight,
                       full_loss=None) -> Tuple[Any, Any]:
        """Per-client (already-weighted) payload for the aggregation
        collective, plus updated aux. delta = server - client.
        ``full_loss`` is provided when ``needs_full_loss`` is set."""
        return tree_scale(delta, weight), client_aux

    def payload_batch_transform(self, payloads):
        """Uplink wire-format transform on the STACKED [k, ...] online
        payloads, applied by the engine AFTER the vmapped client loop
        and BEFORE the aggregation sum. Semantics are per-client
        (leading-axis slices get independent statistics); living outside
        the vmap lets grid-based kernels (the pallas client-grid
        quantizer) serve the uplink, which ``pallas_call`` under vmap
        cannot. Identity by default."""
        return payloads

    def aggregate_transform(self, payload_sum):
        """Downlink wire-format transform of the aggregated payload.

        The engine applies this ONCE after the aggregation collective, so
        ``server_update`` and ``client_post`` consume the SAME transformed
        sum — matching the reference, which re-quantizes the aggregated
        tensor server-side and broadcasts THAT to clients
        (fedavg.py:54-64, fedgate.py:74-79). Identity by default."""
        return payload_sum

    def server_update(self, server_params, server_opt, server_aux,
                      payload_sum, *, online_idx, num_online_eff,
                      client_losses=None):
        """Consume the summed payload; apply the dual-mode server step
        (p -= lr_scale_at_sync * d, fedavg.py:89-94).

        ``online_idx``: [k] int client ids of this round's participants;
        ``num_online_eff``: the weighting denominator (client_weights);
        ``client_losses``: [k] mean local train loss per online client
        (AFL's dual ascent consumes these, afl.py:39-47)."""
        new_params, new_opt = optim.server_step(
            server_params, payload_sum, server_opt,
            self.cfg.optim.lr_scale_at_sync, self.cfg.optim)
        return new_params, new_opt, server_aux

    def client_post(self, *, delta, client_aux, payload_sum, lr,
                    local_steps, server_params, params, weight) -> Any:
        """Per-client aux update that needs the aggregated payload
        (FedGATE's gradient-tracking delta, fedgate.py:102-104). Called
        under vmap over the online clients; ``params`` are the client's
        local params at round end, ``lr`` its final local LR."""
        return client_aux

    # -- payload accounting ----------------------------------------------
    def payload_scale(self) -> float:
        """Fraction of dense float32 bytes the wire format costs
        (1.0 dense, 0.25 int8, ...). Used for comm_bytes metrics."""
        fed = self.cfg.federated
        if fed.quantized:
            return fed.quantized_bits / 32.0
        if fed.compressed:
            return fed.compressed_ratio
        return 1.0
