"""Client-fusion gate: the EXECUTION axis of the round-program
builder (parallel/round_program.py) — which configurations may pack
clients into grouped convolutions.

``cfg.mesh.client_fusion='fused'`` replaces the engine's
``vmap(client_round)`` model compute with one
``feature_group_count=k`` grouped convolution per layer
(models/common.py "client-fused layers") — k x the MXU output lanes
per pass on the 16-64-channel north-star convs that pin MFU at 3.37%
against the ~29% analytic roofline (docs/performance.md). The fused
step is only a different LOWERING of the same per-client math, so it
is gated to configurations where that equivalence is total
(:func:`fusion_supported` is the execution-axis precondition the
round-program cell validator consults; the one fused gate that is NOT
here — commit x fused, a dispatch-axis interaction — lives with the
rest of the composition matrix in ``round_program.validate_cell``):

* the (arch, dataset, norm) triple has a fused module
  (models.define_fused_model — resnet-cifar family + cnn, norm='bn');
* the algorithm runs the BASE local step (``FedAlgorithm.local_step``
  not overridden): its per-client hooks (extra_loss, transform_grads,
  client_payload) are then executed under ``vmap`` by the fused round
  and stay exact for arbitrary hook code, while the model fwd/bwd is
  hand-fused. Personalized algorithms override local_step with their
  own model applies and keep the vmap path;
* no per-step val batch, no full-data loss phase, no recurrent carry,
  no adversarial-noise param, no MoE aux loss — features the fused
  forward does not thread.

The single-device rule (the packed channel axis must not be sharded;
the vmap path's client-axis sharding is the multi-chip strategy) is
NOT here: like commit x fused it is a composition-matrix fact, so
``round_program.illegal_reason`` owns it — one validator, one named
refusal, same message for a resolved trainer and for matrix
enumeration.

``resolve_client_fusion`` applies the config policy on top: 'vmap'
and 'fused' are explicit pins ('fused' raises when unsupported —
silent fallback would invalidate an A/B the user asked for); 'auto'
resolves to 'vmap' (PERF.md section 6, PR 29, holds the chip's one
reading of 'fused'; ROADMAP Design 3 what follows from it).
"""
from __future__ import annotations

from typing import Optional, Tuple

from fedtorch_tpu.algorithms.base import FedAlgorithm
from fedtorch_tpu.config import ExperimentConfig
from fedtorch_tpu.models import define_fused_model
from fedtorch_tpu.models.common import ModelDef


def fusion_supported(cfg: ExperimentConfig, model: ModelDef,
                     algorithm: FedAlgorithm, mesh_devices: int,
                     k_online: int) -> Tuple[Optional[object], str]:
    """(fused_module, "") when the round program can run client-fused,
    else (None, reason)."""
    if type(algorithm).local_step is not FedAlgorithm.local_step:
        return None, (f"algorithm {algorithm.name!r} overrides "
                      "local_step (personalized/custom local loops run "
                      "their own model applies)")
    if algorithm.needs_full_loss:
        return None, (f"algorithm {algorithm.name!r} needs the "
                      "full-data loss phase")
    if algorithm.needs_val_batch:
        return None, (f"algorithm {algorithm.name!r} consumes per-step "
                      "validation batches")
    if model.is_recurrent:
        return None, "recurrent models thread a hidden carry"
    if model.has_noise_param:
        return None, "robust_* archs carry an adversarial noise param"
    if model.has_aux_loss:
        return None, "MoE aux-loss models are not fused"
    if model.is_regression:
        return None, "regression criteria are not fused"
    del mesh_devices  # the multi-device refusal is validate_cell's
    fused = define_fused_model(cfg, k_online)
    if fused is None:
        return None, (f"no fused module for arch="
                      f"{cfg.model.arch!r} / dataset="
                      f"{cfg.data.dataset!r} / norm={cfg.model.norm!r} "
                      "(supported: resnet-cifar family + cnn with "
                      "norm='bn')")
    return fused, ""


def resolve_client_fusion(cfg: ExperimentConfig, model: ModelDef,
                          algorithm: FedAlgorithm, mesh_devices: int,
                          k_online: int) -> Tuple[str, Optional[object]]:
    """Resolve ``cfg.mesh.client_fusion`` ->
    ('vmap'|'fused'|'sequential', module).

    'fused' raises when unsupported; 'auto' resolves to 'vmap' until
    the on-chip fused A/B lands (module docstring)."""
    mode = cfg.mesh.client_fusion
    if mode == "vmap" or mode == "auto":
        return "vmap", None
    if mode == "sequential":
        # the cohort one client after another into a running fold
        # (FederatedTrainer._round_core_sequential); what it cannot
        # serve is refused by round_program.validate_cell
        return "sequential", None
    fused, why = fusion_supported(cfg, model, algorithm, mesh_devices,
                                  k_online)
    if fused is None:
        raise ValueError(
            f"mesh.client_fusion='fused' is unsupported here: {why}")
    return "fused", fused
