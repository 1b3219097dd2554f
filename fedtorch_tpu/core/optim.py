"""Dual-mode functional optimizers.

The reference's custom ``SGD`` (``/root/reference/fedtorch/components/
optimizers/sgd.py:67-129``) has two entry modes sharing one state dict:

* ``step(apply_lr=True)`` — a normal local step: weight decay, *in*-momentum
  buffer, ``p -= lr * d``.
* ``step(apply_lr=False, scale=s, apply_out_momentum=True)`` — the server
  step used by every aggregation rule: no weight decay, *out*-momentum
  buffer, ``p -= s * d`` (``sgd.py:125-128``).

Here both modes are pure functions over parameter/optimizer pytrees, so the
same code runs under ``vmap`` (a batch of per-client optimizers — the
centered mode of the reference) and under ``jit``/``shard_map`` on a mesh.
``AdamW`` mirrors ``optimizers/adam.py:48-104`` including its
``correct_wd`` decoupled-decay switch and the same ``apply_lr=False``
server-step escape hatch (``adam.py:69-70``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from fedtorch_tpu.config import OptimConfig


class SGDState(NamedTuple):
    """Dual momentum buffers, same pytree structure as the params."""
    in_buf: any
    out_buf: any


class AdamState(NamedTuple):
    exp_avg: any
    exp_avg_sq: any
    step: jnp.ndarray  # scalar int32
    out_buf: any       # server-step out-momentum buffer


def init_sgd(params) -> SGDState:
    zeros = jax.tree.map(jnp.zeros_like, params)
    return SGDState(in_buf=zeros, out_buf=jax.tree.map(jnp.zeros_like, params))


def init_adam(params) -> AdamState:
    z = lambda: jax.tree.map(jnp.zeros_like, params)
    return AdamState(exp_avg=z(), exp_avg_sq=z(),
                     step=jnp.zeros((), jnp.int32), out_buf=z())


def _wd_coef(cfg: OptimConfig):
    """Per-leaf weight-decay coefficient function.

    The reference decays EVERY parameter uniformly (sgd.py:96-101
    applies wd to the whole param group — BatchNorm scale/shift and
    biases included), so that stays the default: parity runs against
    the reference would otherwise silently drift. With
    ``cfg.wd_skip_norm_bias`` the standard exclusion applies instead:
    leaves named 'scale' (the zoo's norm layers — BatchStatsNorm and
    GroupNorm both name their affine pair scale/bias) or 'bias' (norm
    shifts and layer biases) get coefficient 0. Resolved from STATIC
    tree paths, so it is free under jit/vmap."""
    wd = cfg.weight_decay

    def coef(path):
        if cfg.wd_skip_norm_bias:
            last = path[-1]
            name = getattr(last, "key", getattr(last, "name", None))
            if name in ("scale", "bias"):
                return 0.0
        return wd

    return coef


def apply_weight_decay(grads, params, cfg: OptimConfig):
    """grads + wd * params, with the per-leaf coefficient rule above."""
    coef = _wd_coef(cfg)
    return jax.tree_util.tree_map_with_path(
        lambda path, g, p: g + coef(path) * p, grads, params)


def _momentum_update(buf, d, factor, dampening, nesterov):
    """buf <- factor*buf + (1-dampening)*d ; returns (direction, new_buf).

    With a zero-initialized buffer this matches the reference's first-step
    special case (sgd.py:103-106) exactly, since mul_(m).add_(d) on zeros
    equals d.
    """
    new_buf = jax.tree.map(
        lambda b, g: factor * b + (1.0 - dampening) * g, buf, d)
    if nesterov:
        direction = jax.tree.map(lambda g, b: g + factor * b, d, new_buf)
    else:
        direction = new_buf
    return direction, new_buf


def sgd_local_step(params, grads, state: SGDState, lr, cfg: OptimConfig):
    """Local (client) step: mirrors sgd.py step(apply_lr=True).

    `lr` may be a traced scalar (per-step scheduled LR).
    """
    if cfg.weight_decay:
        grads = apply_weight_decay(grads, params, cfg)
    in_buf = state.in_buf
    if cfg.in_momentum and cfg.in_momentum_factor:
        grads, in_buf = _momentum_update(
            in_buf, grads, cfg.in_momentum_factor, cfg.dampening,
            cfg.use_nesterov)
    new_params = jax.tree.map(lambda p, d: p - lr * d, params, grads)
    return new_params, SGDState(in_buf=in_buf, out_buf=state.out_buf)


def sgd_server_step(params, direction, state: SGDState, scale,
                    cfg: OptimConfig):
    """Server step: mirrors sgd.py step(apply_lr=False, scale=s,
    apply_out_momentum=True). No weight decay, no LR; out-momentum buffer.

    ``direction`` is the aggregated model delta ("delta-as-grad" trick,
    algorithms/distributed.py:120-126 / fedavg.py:30-34)."""
    out_buf = state.out_buf
    if cfg.out_momentum and cfg.out_momentum_factor:
        direction, out_buf = _momentum_update(
            out_buf, direction, cfg.out_momentum_factor, cfg.dampening,
            cfg.use_nesterov)
    new_params = jax.tree.map(lambda p, d: p - scale * d, params, direction)
    return new_params, SGDState(in_buf=state.in_buf, out_buf=out_buf)


def adam_local_step(params, grads, state: AdamState, lr, cfg: OptimConfig):
    """AdamW local step, mirroring adam.py:71-104 (correct_wd switch)."""
    step = state.step + 1
    b1, b2 = cfg.adam_beta1, cfg.adam_beta2
    if cfg.weight_decay and not cfg.correct_wd:
        # Classic L2-into-gradient (adam.py:77-78 when not correct_wd).
        grads = apply_weight_decay(grads, params, cfg)
    exp_avg = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                           state.exp_avg, grads)
    exp_avg_sq = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                              state.exp_avg_sq, grads)
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    step_size = lr * jnp.sqrt(bc2) / bc1

    coef = _wd_coef(cfg)

    def upd(path, p, m, v):
        new_p = p - step_size * m / (jnp.sqrt(v) + cfg.adam_eps)
        if cfg.weight_decay and cfg.correct_wd:
            # Decoupled weight decay (adam.py:96-97), same per-leaf
            # coefficient rule as the L2 form.
            new_p = new_p - lr * coef(path) * p
        return new_p

    new_params = jax.tree_util.tree_map_with_path(upd, params, exp_avg,
                                                  exp_avg_sq)
    return new_params, AdamState(exp_avg=exp_avg, exp_avg_sq=exp_avg_sq,
                                 step=step, out_buf=state.out_buf)


def adam_server_step(params, direction, state: AdamState, scale,
                     cfg: OptimConfig):
    """Server-step escape hatch (adam.py:69-70): plain p -= scale*d."""
    out_buf = state.out_buf
    if cfg.out_momentum and cfg.out_momentum_factor:
        direction, out_buf = _momentum_update(
            out_buf, direction, cfg.out_momentum_factor, cfg.dampening,
            cfg.use_nesterov)
    new_params = jax.tree.map(lambda p, d: p - scale * d, params, direction)
    return new_params, state._replace(out_buf=out_buf)


# -- Dispatch ---------------------------------------------------------------

def init_opt_state(params, cfg: OptimConfig, lean: bool = False):
    """``lean`` (the sequential execution, parallel/federated.py)
    allocates no SGD buffer whose momentum is off: such a buffer is
    carried through every step and never read."""
    if cfg.optimizer == "sgd":
        if lean:
            zeros = lambda on: jax.tree.map(jnp.zeros_like, params) \
                if on else ()
            return SGDState(
                in_buf=zeros(cfg.in_momentum and cfg.in_momentum_factor),
                out_buf=zeros(cfg.out_momentum
                              and cfg.out_momentum_factor))
        return init_sgd(params)
    if cfg.optimizer in ("adam", "adamw"):
        return init_adam(params)
    raise ValueError(f"Unknown optimizer {cfg.optimizer!r}")


def local_step(params, grads, state, lr, cfg: OptimConfig):
    if isinstance(state, SGDState):
        return sgd_local_step(params, grads, state, lr, cfg)
    return adam_local_step(params, grads, state, lr, cfg)


def server_step(params, direction, state, scale, cfg: OptimConfig):
    if isinstance(state, SGDState):
        return sgd_server_step(params, direction, state, scale, cfg)
    return adam_server_step(params, direction, state, scale, cfg)
