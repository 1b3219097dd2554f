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

Memory: the server's parameters come and go as host arrays, and of the
four model-sized float32 trees a round works with (the server's
parameters, the running sum, the client's parameters, its gradient) at
most three are on the device at once. While a client steps, the device
holds the sum, the client's parameters (updated in place: the step
donates them) and the gradient; the server's parameters are put there
again, leaf by leaf, when the client's movement is folded into the sum,
and that copy is what the next client starts from.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_local_step(loss_fn, lr, weight_decay, cast, param_cast):
    """One jitted SGD step: returns (new params, loss before the step).
    The parameters given are donated."""
    def step(params, x, y):
        value, grads = jax.value_and_grad(loss_fn)(params, x, y, cast)
        new = jax.tree.map(
            lambda p, g: param_cast(p - lr * (g + weight_decay * p)),
            params, grads)
        return new, value
    return jax.jit(step, donate_argnums=0)


def cohort_weight(cohort) -> float:
    k = len(cohort)
    return 1.0 / (k + (0 if 0 in [int(c) for c in cohort] else 1))


def make_round(loss_fn, hp, cast, param_cast, accum_cast):
    """``run_round(server, state, cohort, xs, ys)`` for one reference
    run, its local step traced once. ``server``: the server's
    parameters as host arrays; ``xs``: [k, K, B, ...], ``ys``:
    [k, K, B]. ``state`` is the algorithm's per-client state (none for
    FedAvg). Returns the new server parameters (host arrays), the
    state, and the mean over the cohort of each client's mean loss over
    its K steps."""
    step = make_local_step(loss_fn, hp["lr"], hp["weight_decay"], cast,
                           param_cast)

    def run_round(server, state, cohort, xs, ys):
        return _round(step, server, state, cohort, xs, ys, hp, param_cast,
                      accum_cast)
    return run_round


def _round(step, server, state, cohort, xs, ys, hp, param_cast,
           accum_cast):
    w = cohort_weight(cohort)
    host, tree = jax.tree.flatten(server)

    def on_device(i):
        # a rounding applied to what it has rounded leaves it as it is
        return param_cast(jnp.asarray(host[i], jnp.float32))

    leaves = range(len(host))
    params = [on_device(i) for i in leaves]
    total = [jnp.zeros_like(p) for p in params]
    losses = []
    for c in range(len(cohort)):
        params, client_losses = jax.tree.unflatten(tree, params), []
        for s in range(xs.shape[1]):
            params, value = step(params, xs[c, s], ys[c, s])
            client_losses.append(value)
        losses.append(jnp.mean(jnp.stack(client_losses)))
        params = jax.tree.leaves(params)
        for i in leaves:
            sp = on_device(i)
            total[i] = accum_cast(total[i] + accum_cast(w * (sp - params[i])))
            params[i] = sp      # the next client starts from the server's
    for i in leaves:
        host[i] = jax.device_get(
            param_cast(params[i] - hp["server_lr"] * total[i]))
        params[i] = total[i] = None
    return jax.tree.unflatten(tree, host), state, jnp.mean(jnp.stack(losses))
