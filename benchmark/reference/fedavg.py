"""FedAvg (McMahan et al. 2017) as the launcher runs it, in plain
arithmetic: every client of the cohort starts from the server's
parameters, takes K steps of SGD with weight decay on its own batches,
and the server subtracts the weighted sum of the clients' movements.
One Python loop over the clients and their steps; gradients by
``jax.grad`` of the reference model's loss.

The weights follow FedTorch's ``fedavg.py:18-27``: 1/|cohort| when
client 0 is in the cohort, 1/(|cohort|+1) when it is not (its MPI
server shares rank 0 with a client). Kept because it is what the system
under test promises to reproduce; it is stated in the configuration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_local_step(loss_fn, lr, weight_decay, cast, param_cast):
    """One jitted SGD step: returns (new params, loss before the step)."""
    def step(params, x, y):
        value, grads = jax.value_and_grad(loss_fn)(params, x, y, cast)
        new = jax.tree.map(
            lambda p, g: param_cast(p - lr * (g + weight_decay * p)),
            params, grads)
        return new, value
    return jax.jit(step)


def cohort_weight(cohort) -> float:
    k = len(cohort)
    return 1.0 / (k + (0 if 0 in [int(c) for c in cohort] else 1))


def make_round(loss_fn, hp, cast, param_cast, accum_cast):
    """``run_round(server, state, cohort, xs, ys)`` for one reference
    run, its local step traced once. ``xs``: [k, K, B, ...], ``ys``:
    [k, K, B]. ``state`` is the algorithm's per-client state (none for
    FedAvg). Returns the new server parameters, the state, and the mean
    over the cohort of each client's mean loss over its K steps."""
    step = make_local_step(loss_fn, hp["lr"], hp["weight_decay"], cast,
                           param_cast)

    def run_round(server, state, cohort, xs, ys):
        return _round(step, server, state, cohort, xs, ys, hp, param_cast,
                      accum_cast)
    return run_round


def _round(step, server, state, cohort, xs, ys, hp, param_cast,
           accum_cast):
    w = cohort_weight(cohort)
    total = jax.tree.map(jnp.zeros_like, server)
    losses = []
    for c in range(len(cohort)):
        params, client_losses = server, []
        for s in range(xs.shape[1]):
            params, value = step(params, xs[c, s], ys[c, s])
            client_losses.append(value)
        losses.append(jnp.mean(jnp.stack(client_losses)))
        total = jax.tree.map(
            lambda t, sp, p: accum_cast(t + accum_cast(w * (sp - p))),
            total, server, params)
    new_server = jax.tree.map(
        lambda sp, t: param_cast(sp - hp["server_lr"] * t), server, total)
    return new_server, state, jnp.mean(jnp.stack(losses))
