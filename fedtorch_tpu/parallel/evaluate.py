"""Evaluation.

Parity with ``do_validate`` (comms/utils/eval.py:41-150) and the centered
variants (eval_centered.py): batched inference with loss + top-k accuracy,
aggregated across clients; per-client worst/best/variance summaries
(eval_centered.py:94-113). The reference's metric all-reduce
(``global_average``, algorithms/distributed.py:148-161) is a masked mean
over the client axis here.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from fedtorch_tpu import telemetry
from fedtorch_tpu.core.losses import make_criterion, topk_accuracy
from fedtorch_tpu.models.common import ModelDef, is_token_model
from fedtorch_tpu.utils.tracing import instrument_trace


class EvalResult(NamedTuple):
    loss: jnp.ndarray
    top1: jnp.ndarray
    top5: jnp.ndarray


def forward_fn(model: ModelDef):
    """``(params, x) -> logits`` for any model: recurrent models get a
    fresh zero hidden carry per call (the shared policy for evaluation
    and auxiliary forwards — see FedAlgorithm.forward_reset)."""
    if model.is_recurrent:
        return lambda p, x: model.apply(
            p, x, carry=model.init_carry(x.shape[0]))[0]
    return lambda p, x: model.apply(p, x)


def _pad_batches(x: np.ndarray, y: np.ndarray, batch_size: int):
    n = x.shape[0]
    n_batches = max((n + batch_size - 1) // batch_size, 1)
    pad = n_batches * batch_size - n
    if pad:
        # cycle rows so padding works even when pad > n (tiny eval sets)
        idx = np.arange(pad) % n
        x = np.concatenate([x, x[idx]])
        y = np.concatenate([y, y[idx]])
    mask = np.concatenate([np.ones(n), np.zeros(pad)])
    return (x.reshape((n_batches, batch_size) + x.shape[1:]),
            # y may be [N] class labels or [N, T] sequence targets
            y.reshape((n_batches, batch_size) + y.shape[1:]),
            mask.reshape(n_batches, batch_size))


# jitted-callable caches keyed on the (hashable) flax module + flags, so
# repeated evaluate() calls in the driver loop reuse one traced program
# instead of re-tracing a fresh closure every round
_ASCENT_CACHE = {}
_EVAL_CACHE = {}


def _ascent_on_batches(model: ModelDef, params, bx, by, bm,
                       step_size: float = 0.01):
    """Noise-ascent core over pre-padded batches (masked so padding rows
    contribute nothing to the ascent gradient)."""
    from fedtorch_tpu.core.losses import per_sample_loss

    key = (model.module, model.is_regression, step_size)
    if key not in _ASCENT_CACHE:
        def run(params, bx, by, bm):
            def body(params, batch):
                xb, yb, mb = batch

                def loss_fn(noise):
                    p = dict(params, noise=noise)
                    logits = model.apply(p, xb)
                    per = per_sample_loss(logits, yb, model.is_regression)
                    return jnp.sum(per * mb) / jnp.maximum(jnp.sum(mb),
                                                           1.0)

                g = jax.grad(loss_fn)(params["noise"])
                noise = params["noise"] + step_size * g
                norm = jnp.linalg.norm(noise)
                noise = jnp.where(norm > 1.0, noise / norm, noise)
                return dict(params, noise=noise), None

            params, _ = jax.lax.scan(body, params, (bx, by, bm))
            return params

        # caller reuses params after the ascent, so donation is unsafe
        # lint: disable=FTL004 — caller reuses the params buffers
        _ASCENT_CACHE[key] = jax.jit(
            instrument_trace("evaluate.ascent", run))
    return _ASCENT_CACHE[key](params, bx, by, bm)


def robust_noise_ascent(model: ModelDef, params, x: np.ndarray,
                        y: np.ndarray, batch_size: int = 256,
                        step_size: float = 0.01):
    """Adversarial evaluation prelude for robust_* archs
    (eval.py:59-68): one gradient-ascent pass over the eval set on the
    learnable input-noise parameter, projecting onto the unit ball after
    each step. Returns params with the adversarially-updated noise."""
    if not model.has_noise_param:
        return params
    bx, by, bm = _pad_batches(np.asarray(x), np.asarray(y), batch_size)
    return _ascent_on_batches(model, params, jnp.asarray(bx),
                              jnp.asarray(by), jnp.asarray(bm), step_size)


def evaluate(model: ModelDef, params, x: np.ndarray, y: np.ndarray,
             batch_size: int = 256,
             robust_ascent: bool = True) -> EvalResult:
    """Server-side test evaluation (eval.py:83-99 inference loop),
    scanning over batches on device with padding masks. Robust archs get
    the adversarial noise-ascent prelude (eval.py:59-68) unless
    ``robust_ascent=False``."""
    token = is_token_model(model)
    if token:
        # a row is thousands of tokens: a few rows a step
        batch_size = model.eval_batch
    # the call's host half under spans of its own: the padded copy of
    # the whole test set and its upload are fresh buffers of the set's
    # size every call
    with telemetry.span("eval.batches") as sp:
        bx, by, bm = _pad_batches(np.asarray(x), np.asarray(y), batch_size)
        nbytes = bx.nbytes + by.nbytes + bm.nbytes
        sp.note(bytes=nbytes, rows=len(x), pad_rows=bm.size - len(x))
    with telemetry.span("eval.h2d", bytes=nbytes):
        bx, by, bm = jnp.asarray(bx), jnp.asarray(by), jnp.asarray(bm)
    with telemetry.span("eval.dispatch"):
        if model.has_noise_param and robust_ascent:
            # pad/upload once; the ascent shares the same device batches
            params = _ascent_on_batches(model, params, bx, by, bm)

        # a token model is a tuple of hashables, dtype and backend among
        # them: the whole of it is the key
        key = model if token else \
            (model.module, model.is_regression, model.is_recurrent)
        if key not in _EVAL_CACHE:
            # params is the live server model, reused every round —
            # donation would be unsafe here
            _EVAL_CACHE[key] = jax.jit(
                instrument_trace("evaluate.run", _eval_run_fn(model)))
        return _EVAL_CACHE[key](params, bx, by, bm)


def evaluate_to_host(model: ModelDef, params, x: np.ndarray,
                     y: np.ndarray) -> EvalResult:
    """:func:`evaluate` and the ONE transfer of its result to the host
    (span ``eval.fetch``: the wait for the device is in here)."""
    res = evaluate(model, params, x, y)
    with telemetry.span("eval.fetch"):
        return jax.device_get(res)


def _eval_run_fn(model: ModelDef):
    """The eval program body, shared by the cached live jit above and
    the uninstrumented cost-capture twin (:func:`lowered_eval_program`)
    so the two lower the same program by construction."""
    def run(params, bx, by, bm):
        def body(carry, batch):
            xb, yb, mb = batch
            if model.is_recurrent:
                logits, _ = model.apply(
                    params, xb, carry=model.init_carry(xb.shape[0]))
            else:
                logits = model.apply(params, xb)
            if is_token_model(model):
                # next-token loss and top-k: position t's logits
                # against token t + 1 of the same row
                logits, yb = logits[:, :-1], xb[:, 1:]
            if logits.ndim == 3:
                # sequence model ([B, T, V] logits, [B, T] targets):
                # per-token statistics over the flattened time axis
                mb_f = jnp.repeat(mb, yb.shape[-1])
                logits = logits.reshape(-1, logits.shape[-1])
                yb_f = yb.reshape(-1)
            else:
                yb_f, mb_f = yb, mb
            # per-sample statistics masked so padding rows (duplicates
            # of the head of the split) contribute nothing
            if model.is_regression:
                per = jnp.square(logits.reshape(-1) - yb_f)
                t1 = t5 = jnp.zeros_like(per)
            else:
                logp = jax.nn.log_softmax(logits)
                per = -jnp.take_along_axis(
                    logp, yb_f[:, None].astype(jnp.int32),
                    axis=-1)[:, 0]
                kmax = min(5, logits.shape[-1])
                _, pred = jax.lax.top_k(logits, kmax)
                correct = pred == yb_f[:, None].astype(pred.dtype)
                t1 = correct[:, 0].astype(jnp.float32)
                t5 = jnp.any(correct, axis=1).astype(jnp.float32)
            return carry, (jnp.sum(per * mb_f), jnp.sum(t1 * mb_f),
                           jnp.sum(t5 * mb_f), jnp.sum(mb_f))

        with jax.named_scope("eval.forward"):
            _, (losses, t1s, t5s, ws) = jax.lax.scan(body, 0,
                                                     (bx, by, bm))
            total = jnp.maximum(jnp.sum(ws), 1e-8)
            return EvalResult(jnp.sum(losses) / total,
                              jnp.sum(t1s) / total, jnp.sum(t5s) / total)

    return run


def lowered_eval_program(model: ModelDef, params, x: np.ndarray,
                         y: np.ndarray, batch_size: int = 256):
    """AOT-lower the eval program (an uninstrumented twin of the
    cached live jit — same body via :func:`_eval_run_fn`, so the HLO
    is identical) against abstract padded-batch inputs: the ``eval``
    entry of ``program_costs.json`` (telemetry.costs). Lowering
    executes nothing on device."""
    if is_token_model(model):
        batch_size = model.eval_batch
    bx, by, bm = _pad_batches(np.asarray(x), np.asarray(y), batch_size)
    sds = jax.ShapeDtypeStruct
    return jax.jit(_eval_run_fn(model)).lower(
        params, sds(bx.shape, bx.dtype), sds(by.shape, by.dtype),
        sds(bm.shape, bm.dtype))


def evaluate_clients(model: ModelDef, client_params, data,
                     batch_size: int = 64, max_batches: int = 8,
                     apply_fn=None):
    """Per-client evaluation on per-client (val) shards: returns [C] loss
    and accuracy, plus the worst/best/variance summary the centered mode
    logs (eval_centered.py:94-113).

    ``apply_fn(per_client_params, x) -> logits`` overrides the default
    forward (used by personalized evaluation); ``client_params`` is any
    pytree with a leading client axis that apply_fn understands."""
    criterion = make_criterion(model.is_regression)
    n_b = min(max_batches, max(data.n_max // batch_size, 1))

    if apply_fn is None:
        apply_fn = forward_fn(model)

    # lint: disable=FTL004 — client_params stay live in the trainer
    @jax.jit
    def run(client_params, data):
        def one(params, x, y, size):
            def body(carry, i):
                idx = (i * batch_size + jnp.arange(batch_size)) \
                    % jnp.maximum(size, 1)
                xb, yb = x[idx], y[idx]
                logits = apply_fn(params, xb)
                loss = criterion(logits, yb)
                acc = jnp.asarray(0.0) if model.is_regression else \
                    topk_accuracy(logits, yb, (1,))[0]
                return carry, (loss, acc)

            _, (losses, accs) = jax.lax.scan(body, 0, jnp.arange(n_b))
            return jnp.mean(losses), jnp.mean(accs)

        return jax.vmap(one)(client_params, data.x, data.y, data.sizes)

    losses, accs = run(client_params, data)
    # size-0 clients are mesh-padding (pad_client_axis) — exclude them
    # from the cross-client summaries. Masked on-device reductions: the
    # per-client arrays may span non-addressable devices on a multi-host
    # mesh, where only replicated scalars can be fetched. The five
    # summary scalars come back in ONE batched device_get instead of
    # five blocking per-metric transfers (this call sits in the
    # per-round eval path — fedtorch_tpu.lint FTL001).
    valid = jnp.asarray(data.sizes) > 0
    n = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)
    acc_mean = jnp.sum(jnp.where(valid, accs, 0.0)) / n
    summary = {
        "loss_mean": jnp.sum(jnp.where(valid, losses, 0.0)) / n,
        "acc_mean": acc_mean,
        "acc_worst": jnp.min(jnp.where(valid, accs, jnp.inf)),
        "acc_best": jnp.max(jnp.where(valid, accs, -jnp.inf)),
        "acc_var": jnp.sum(
            jnp.where(valid, jnp.square(accs - acc_mean), 0.0)) / n,
    }
    summary = {k: float(v) for k, v in
               jax.device_get(summary).items()}
    return losses, accs, summary


_PER_CLASS_CACHE = {}


def evaluate_per_class(model: ModelDef, params, x: np.ndarray,
                       y: np.ndarray, num_classes: int,
                       batch_size: int = 256,
                       robust_ascent: bool = True):
    """Per-class accuracy (components/metrics.py:77-91; --per_class_acc
    flag, parameters.py:98-99): returns [num_classes] accuracy plus the
    per-class sample counts. Robust archs get the same adversarial
    noise-ascent prelude as :func:`evaluate`, keeping the decomposition
    consistent with the reported top1."""
    from fedtorch_tpu.core.losses import per_class_accuracy
    bx, by, bm = _pad_batches(np.asarray(x), np.asarray(y), batch_size)
    bx, by, bm = jnp.asarray(bx), jnp.asarray(by), jnp.asarray(bm)
    if model.has_noise_param and robust_ascent:
        params = _ascent_on_batches(model, params, bx, by, bm)

    key = (model.module, model.is_recurrent, num_classes)
    if key not in _PER_CLASS_CACHE:
        def run(params, bx, by, bm):
            def body(carry, batch):
                xb, yb, mb = batch
                if model.is_recurrent:
                    logits, _ = model.apply(
                        params, xb, carry=model.init_carry(xb.shape[0]))
                else:
                    logits = model.apply(params, xb)
                if logits.ndim == 3:
                    mb = jnp.repeat(mb, yb.shape[-1])
                    logits = logits.reshape(-1, logits.shape[-1])
                    yb = yb.reshape(-1)
                correct, total = per_class_accuracy(logits, yb,
                                                    num_classes, mask=mb)
                c_sum, t_sum = carry
                return (c_sum + correct, t_sum + total), None

            (c_sum, t_sum), _ = jax.lax.scan(
                body, (jnp.zeros(num_classes), jnp.zeros(num_classes)),
                (bx, by, bm))
            return c_sum / jnp.maximum(t_sum, 1.0), t_sum

        # params is the live server model: donation unsafe
        _PER_CLASS_CACHE[key] = jax.jit(
            instrument_trace("evaluate.per_class", run))
    return _PER_CLASS_CACHE[key](params, bx, by, bm)


def evaluate_personal(model: ModelDef, client_aux, client_params, data,
                      algorithm_name: str, batch_size: int = 64,
                      max_batches: int = 8):
    """Per-client evaluation of personalized models — evaluated against
    the PRE-aggregation local model snapshot the algorithms keep in aux
    (the reference validates personal models before the sync,
    apfl.py:138-144).

    * apfl: mixed output alpha*personal + (1-alpha)*local_snapshot
      (inference_personal, eval.py:31-39)
    * perfedme: the personal model theta
    * perfedavg: the adapted pre-sync local model
    """
    if algorithm_name == "apfl":
        eval_params = (client_aux["personal"],
                       client_aux["local_snapshot"], client_aux["alpha"])
        fwd = forward_fn(model)
        apply_fn = lambda ps, x: ps[2] * fwd(ps[0], x) \
            + (1 - ps[2]) * fwd(ps[1], x)
    elif algorithm_name == "perfedme":
        eval_params = client_aux["personal"]
        apply_fn = None
    elif algorithm_name == "perfedavg":
        eval_params = client_aux["local_snapshot"]
        apply_fn = None
    else:
        eval_params = client_params
        apply_fn = None
    return evaluate_clients(model, eval_params, data,
                            batch_size=batch_size,
                            max_batches=max_batches, apply_fn=apply_fn)
