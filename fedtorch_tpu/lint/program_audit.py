"""Program-level audit: static checks over the LOWERED round programs.

The AST gate (``analyzer.py``) catches tracing hazards in *source*;
the invariants the engine actually leans on — bf16 programs that stay
bf16, donated buffers that really alias, one collective per round, no
host chatter inside the program body, no data baked into the
executable — live in the *lowered* XLA artifact, where nothing checks
them until silicon time. This module abstractly lowers every legal
cell of the round-program builder matrix (the same UNINSTRUMENTED AOT
twins ``telemetry/costs.py`` cost-captures, against
``jax.eval_shape``-derived state structs — no training executes, no
device buffer is allocated for model state) on whatever backend is
active (CPU in tier-1) and statically checks the StableHLO text and
jaxpr constants for the FTP rules (ids/hints in ``rules.py``):

* **FTP001** — unintended dtype promotion: any ``f64`` tensor, and
  ``f32`` matmul/convolution operands inside a bf16-configured
  program (the MXU-rate contract of ``--compute_dtype bfloat16``).
* **FTP002** — host transfers in the program body: infeed/outfeed/
  send/recv ops or host-callback ``custom_call`` targets. A
  ``jax.debug.print`` that sneaks into a round program pins a host
  round-trip into every execution.
* **FTP003** — donation ineffectiveness: the round programs donate
  ``(server, clients)``; every donated leaf must carry a
  ``tf.aliasing_output`` attribute in the lowered module, else the
  program holds both generations of that buffer (the 2x-HBM failure
  FTL004 approximates at source level, checked here on the artifact).
* **FTP004** — collective count above the cell's budget
  (``round_program.collective_budget``: one aggregation collective
  per round, scaled by scan length; zero on single-device meshes).
* **FTP005** — large constants baked into the program (an FTL002
  numpy leak that survived to lowering): any jaxpr const over
  ``LARGE_CONST_BYTES``.
* **FTP006** — peak-HBM regression vs the checked-in
  ``lint/program_baseline.json``: when a cell has a recorded
  ``peak_hbm_bytes`` the compiled program's watermark
  (``telemetry.costs.cost_summary``) may not exceed it by more than
  ``PEAK_HBM_TOLERANCE``. Cells without a recorded peak are not
  checked (the shipped baseline is empty; ``--write-baseline``
  records the current watermarks to arm the regression gate).

Findings share the fingerprint/suppression/baseline machinery of
``findings.py`` — the baseline file is a multiset of accepted
fingerprints plus the per-cell peak map, diffed exactly like the AST
gate's. The pure text checks take HLO text in, findings out, so tests
seed violations without building trainers; the cell-lowering half
(the only part that imports jax) reuses the builder's own
cell-enumeration hook (``round_program.cell_build_facts``) and the
trainers' ``lowered_cost_programs`` twins.

Entry points: ``fedtorch-tpu audit`` / ``python -m fedtorch_tpu.lint
--audit`` (docs/static_analysis.md "The program audit").
"""
from __future__ import annotations

import json
import os
import re
import time
from collections import Counter
from typing import Dict, List, Optional, Tuple

from fedtorch_tpu.lint.findings import Finding, diff_against_baseline
from fedtorch_tpu.lint.rules import hint_for

PROGRAM_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "program_baseline.json")
PROGRAM_BASELINE_VERSION = 1

# a jaxpr const this large baked into the executable is data, not a
# config scalar — it re-uploads per compile and bloats the binary
LARGE_CONST_BYTES = 64 * 1024

# relative headroom before a peak-HBM watermark counts as a regression
PEAK_HBM_TOLERANCE = 0.05

# custom_call targets that are program metadata, not host transfers
_BENIGN_CUSTOM_CALLS = {
    "Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
    "LayoutConstraint", "annotate_device_placement",
}

_TRANSFER_OPS = ("infeed", "outfeed", "send", "recv")
_COLLECTIVE_OPS = ("all_reduce", "all_gather", "all_to_all",
                   "reduce_scatter", "collective_permute",
                   "collective_broadcast")

# the scan-of-R length the audit lowers (small: the checks are
# structural, not scale-dependent)
AUDIT_SCAN_LENGTH = 2


def _finding(cell: str, rule: str, message: str, evidence: str = ""
             ) -> Finding:
    """Findings are keyed by cell, not file:line — the ``path`` slot
    carries the program name so the shared fingerprint machinery
    (path:rule:normalized evidence) stays meaningful."""
    return Finding(path=f"program:{cell}", line=0, col=0, rule=rule,
                   message=message, hint=hint_for(rule),
                   source_line=evidence)


# -- pure StableHLO text checks (stdlib; unit-tested on seeded text) -----

_F64_RE = re.compile(r"tensor<(?:\d+x)*f64>|\bf64\[")
_MXU_OP_RE = re.compile(r"stablehlo\.(dot_general|dot|convolution)\b")
_CUSTOM_CALL_RE = re.compile(r"custom_call\s*@([\w.$]+)")
# single-device lowerings resolve aliasing AT LOWERING and stamp
# `tf.aliasing_output = N`; sharded lowerings defer the pairing to
# compile time and stamp `jax.buffer_donor = true`. Either marks the
# donation as established — a donated-but-unaliasable leaf gets
# NEITHER (jax warns and drops it), which is what FTP003 catches.
_ALIASED_PARAM_RE = re.compile(
    r"tf\.aliasing_output|jax\.buffer_donor")


def check_dtype_promotion(hlo_text: str, cell: str, *,
                          compute_dtype: str = "float32"
                          ) -> List[Finding]:
    """FTP001: f64 anywhere; f32 matmul/conv operands when the cell is
    bf16-configured."""
    out: List[Finding] = []
    m = _F64_RE.search(hlo_text)
    if m:
        line = next(ln for ln in hlo_text.splitlines() if m.group(0) in ln)
        out.append(_finding(
            cell, "FTP001",
            "f64 tensor in the lowered program — double precision "
            "runs at a fraction of peak and nothing here wants it",
            line.strip()[:160]))
    if compute_dtype == "bfloat16":
        for ln in hlo_text.splitlines():
            if not _MXU_OP_RE.search(ln):
                continue
            # operand types are the parenthesized list before `->`
            sig = ln.split(" : ", 1)[-1].split("->", 1)[0]
            if "xf32>" in sig or "tensor<f32>" in sig:
                out.append(_finding(
                    cell, "FTP001",
                    "f32 matmul/conv operand inside a bf16-configured "
                    "program — the MXU runs at half rate on this op",
                    ln.strip()[:160]))
    return out


def check_host_transfers(hlo_text: str, cell: str) -> List[Finding]:
    """FTP002: transfer ops / host-callback custom_calls in the body."""
    out: List[Finding] = []
    for ln in hlo_text.splitlines():
        stripped = ln.strip()
        if any(f"stablehlo.{op}" in stripped or f" {op}(" in stripped
               for op in _TRANSFER_OPS):
            out.append(_finding(
                cell, "FTP002",
                "host-transfer op inside the program body",
                stripped[:160]))
            continue
        m = _CUSTOM_CALL_RE.search(stripped)
        if m and m.group(1) not in _BENIGN_CUSTOM_CALLS:
            out.append(_finding(
                cell, "FTP002",
                f"custom_call to {m.group(1)!r} — a host callback / "
                "opaque transfer inside the program body",
                stripped[:160]))
    return out


def check_donation(hlo_text: str, cell: str, donated_leaves: int
                   ) -> List[Finding]:
    """FTP003: every donated input leaf must alias an output."""
    if donated_leaves <= 0:
        return []
    aliased = len(_ALIASED_PARAM_RE.findall(hlo_text))
    if aliased >= donated_leaves:
        return []
    return [_finding(
        cell, "FTP003",
        f"only {aliased} of {donated_leaves} donated input leaves "
        "alias an output buffer — the unaliased state is held twice "
        "for the program's lifetime",
        f"aliased={aliased} donated={donated_leaves}")]


def check_collectives(hlo_text: str, cell: str, budget: int, *,
                      exact: bool = False) -> List[Finding]:
    """FTP004: cross-device collective count vs the cell's budget.

    ``exact=True`` is the pod-scale certification
    (``client_shards > 1`` cells): the budget is a floor AND a
    ceiling — the one explicit client-axis all-reduce of
    ``podscale.cohort_hierarchical_sum`` must be present (a missing
    collective means the sharded seam silently fell back to a
    replicated sum) and nothing may add a second synchronization
    point."""
    count = 0
    for op in _COLLECTIVE_OPS:
        count += len(re.findall(
            rf"stablehlo\.{op}\b|\b{op.replace('_', '-')}\b", hlo_text))
    if exact and count < budget:
        return [_finding(
            cell, "FTP004",
            f"{count} collective op(s) under the sharded cell's exact "
            f"budget of {budget} — the client-axis hierarchical sum's "
            "explicit all-reduce did not lower (replicated fallback?)",
            f"collectives={count} budget={budget} exact")]
    if count <= budget:
        return []
    return [_finding(
        cell, "FTP004",
        f"{count} collective op(s) exceed the cell's budget of "
        f"{budget} — a second synchronization point grew into the "
        "round program",
        f"collectives={count} budget={budget}"
        + (" exact" if exact else ""))]


def check_large_constants(consts: List[Tuple[str, int]], cell: str
                          ) -> List[Finding]:
    """FTP005: ``consts`` is [(shape/dtype description, nbytes)] from
    the traced jaxpr's closed-over constants."""
    out = []
    for desc, nbytes in consts:
        if nbytes > LARGE_CONST_BYTES:
            out.append(_finding(
                cell, "FTP005",
                f"{nbytes}-byte constant baked into the program "
                f"({desc}) — data captured at trace time instead of "
                "passed as an argument",
                desc))
    return out


def check_peak_hbm(peak: Optional[float], cell: str,
                   baseline_peaks: Dict[str, float]) -> List[Finding]:
    """FTP006: regression vs the recorded watermark (skipped when the
    cell has no recorded peak, or the backend reports none)."""
    recorded = baseline_peaks.get(cell)
    if recorded is None or peak is None:
        return []
    if peak <= recorded * (1.0 + PEAK_HBM_TOLERANCE):
        return []
    return [_finding(
        cell, "FTP006",
        f"peak-HBM watermark {peak:.0f} B exceeds the recorded "
        f"{recorded:.0f} B by more than "
        f"{PEAK_HBM_TOLERANCE:.0%}",
        f"peak={peak:.0f} recorded={recorded:.0f}")]


# -- the program baseline (fingerprints multiset + peak map) -------------

def load_program_baseline(path: str = PROGRAM_BASELINE
                          ) -> Tuple[Counter, Dict[str, float]]:
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except OSError:
        return Counter(), {}
    fps = Counter({k: int(v) for k, v in
                   doc.get("fingerprints", {}).items()})
    peaks = {k: float(v) for k, v in
             doc.get("peak_hbm_bytes", {}).items()}
    return fps, peaks


def save_program_baseline(path: str, findings: List[Finding],
                          peaks: Dict[str, float]) -> None:
    counts = Counter(f.fingerprint() for f in findings)
    doc = {
        "version": PROGRAM_BASELINE_VERSION,
        "comment": "Accepted fedtorch-tpu audit findings + per-cell "
                   "peak-HBM watermarks. Regenerate with: "
                   "fedtorch-tpu audit --write-baseline "
                   "(docs/static_analysis.md).",
        "fingerprints": {k: counts[k] for k in sorted(counts)},
        "peak_hbm_bytes": {k: peaks[k] for k in sorted(peaks)},
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")


# -- cell lowering (the only half that imports jax) ----------------------

def _audit_config(source: str, dispatch: str, execution: str,
                  compute_dtype: str = "float32",
                  client_shards: int = 0):
    """The tiny canonical audit config for one cell — the same shapes
    the builder-matrix tests pin, built through the cell-enumeration
    hook so cell-to-config mapping cannot drift from the axes."""
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.parallel.round_program import cell_build_facts

    facts = cell_build_facts(source, dispatch, execution,
                             client_shards=client_shards)
    return ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=16,
                        batch_size=8, synthetic_alpha=0.5,
                        synthetic_beta=0.5,
                        data_plane=facts["data_plane"]),
        federated=FederatedConfig(
            federated=True, num_clients=8, online_client_rate=0.5,
            algorithm="fedavg", sync_type="local_step",
            sync_mode=facts["sync_mode"]),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.3, weight_decay=0.0),
        train=TrainConfig(local_step=2),
        # the sequential fold is one device's
        mesh=MeshConfig(num_devices=1 if execution == "sequential"
                        else None,
                        client_fusion=facts["client_fusion"],
                        compute_dtype=compute_dtype,
                        client_shards=facts["client_shards"]),
    ).finalize()


def _build_cell_trainer(source: str, dispatch: str, execution: str,
                        compute_dtype: str = "float32",
                        client_shards: int = 0):
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer

    cfg = _audit_config(source, dispatch, execution, compute_dtype,
                        client_shards)
    data = build_federated_data(cfg).train
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    if cfg.federated.sync_mode == "async":
        from fedtorch_tpu.async_plane import AsyncFederatedTrainer
        return AsyncFederatedTrainer(cfg, model, make_algorithm(cfg),
                                     data)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data)


def lower_cell(source: str, dispatch: str, execution: str, *,
               compute_dtype: str = "float32",
               scan_length: int = AUDIT_SCAN_LENGTH,
               client_shards: int = 0) -> Dict:
    """Lower one legal cell's uninstrumented twin and return the audit
    evidence: StableHLO text, jaxpr consts, donated-leaf count, and
    the ``jax.stages.Lowered`` (for optional FTP006 compiles).

    State comes from ``jax.eval_shape`` over ``init_state`` — no
    parameter buffer is materialized and nothing executes.
    ``client_shards > 1`` lowers the cell's pod-scale mesh'd twin
    (client axis over S device groups) for the FTP004 exact-count
    certification."""
    import jax

    trainer = _build_cell_trainer(source, dispatch, execution,
                                  compute_dtype, client_shards)
    server, clients = jax.eval_shape(trainer.init_state,
                                     jax.random.key(0))
    if dispatch == "scan":
        programs, _ = trainer.lowered_cost_programs(
            server, clients, num_scan_rounds=scan_length)
        name = next(k for k in programs if "scan" in k)
    else:
        programs, name = trainer.lowered_cost_programs(server, clients)
    lowered = programs[name]

    # the same twin, traced for its closed-over constants (FTP005)
    if dispatch == "commit":
        consts = []  # the commit twin's jobs struct is abstract; the
        # commit program shares _round_core with the round programs,
        # whose consts the round cells already audit
    else:
        fn, args = _twin_trace_args(trainer, dispatch, server, clients,
                                    scan_length)
        traced = jax.jit(fn, donate_argnums=(0, 1)).trace(*args)
        consts = [(f"{getattr(c, 'dtype', '?')}"
                   f"{list(getattr(c, 'shape', ()))}",
                   _const_nbytes(c)) for c in traced.jaxpr.consts]

    donated_leaves = len(jax.tree.leaves((server, clients)))
    return {
        "cell": _cell_label(source, dispatch, execution, compute_dtype,
                            client_shards),
        "axes": (source, dispatch, execution),
        "program": name,
        "lowered": lowered,
        "text": lowered.as_text(),
        "consts": consts,
        "donated_leaves": donated_leaves,
        "mesh_devices": int(trainer.mesh.devices.size),
        "client_shards": int(client_shards),
    }


def _twin_trace_args(trainer, dispatch, server, clients, scan_length):
    if dispatch == "round":
        if trainer.data_plane == "stream":
            return trainer.round_stream_fn, (
                server, clients, trainer._feed_struct())
        return trainer.round_fn, (server, clients, trainer.data,
                                  trainer.val_data)
    fn = trainer.programs.build("scan", scan_length=scan_length)
    if trainer.data_plane == "stream":
        return fn, (server, clients,
                    trainer._window_struct(scan_length))
    return fn, (server, clients, trainer.data, trainer.val_data)


def _const_nbytes(c) -> int:
    import numpy as np
    shape = getattr(c, "shape", ())
    dtype = getattr(c, "dtype", None)
    itemsize = np.dtype(dtype).itemsize if dtype is not None else 8
    n = 1
    for d in shape:
        n *= int(d)
    return n * itemsize


def _cell_label(source, dispatch, execution, compute_dtype,
                client_shards: int = 0) -> str:
    from fedtorch_tpu.parallel.round_program import cell_name
    label = cell_name(source, dispatch, execution)
    if compute_dtype != "float32":
        label += f"[{compute_dtype}]"
    if client_shards > 1:
        label += f"[shards={client_shards}]"
    return label


def audit_cell_evidence(ev: Dict, *, compute_dtype: str = "float32",
                        num_rounds: int = 1,
                        baseline_peaks: Optional[Dict[str, float]] = None,
                        peak: Optional[float] = None) -> List[Finding]:
    """All FTP checks over one cell's collected evidence."""
    from fedtorch_tpu.parallel.round_program import collective_budget

    cell, text = ev["cell"], ev["text"]
    src, disp, exe = ev["axes"]
    shards = int(ev.get("client_shards", 0))
    budget = collective_budget(src, disp, exe,
                               mesh_devices=ev["mesh_devices"],
                               num_rounds=num_rounds,
                               client_shards=shards)
    findings = []
    findings += check_dtype_promotion(text, cell,
                                      compute_dtype=compute_dtype)
    findings += check_host_transfers(text, cell)
    findings += check_donation(text, cell, ev["donated_leaves"])
    findings += check_collectives(text, cell, budget,
                                  exact=shards > 1)
    findings += check_large_constants(ev["consts"], cell)
    findings += check_peak_hbm(peak, cell, baseline_peaks or {})
    return findings


# bf16 twins: the vmap round/scan cells re-lower bf16-configured so the
# f32-in-bf16 half of FTP001 has a live program to check
BF16_CELLS = (("resident", "round", "vmap"), ("feed", "round", "vmap"),
              ("resident", "scan", "vmap"), ("feed", "scan", "vmap"))

# pod-scale twins: every legal vmap cell re-lowers with the client axis
# sharded this many ways (when the backend has the devices) so FTP004
# certifies EXACTLY one explicit client-axis all-reduce per
# round/commit program (docs/performance.md "Pod-scale round programs")
PODSCALE_SHARDS = 2


def audit_programs(*, baseline_path: str = PROGRAM_BASELINE,
                   write_baseline: bool = False,
                   scan_length: int = AUDIT_SCAN_LENGTH,
                   include_bf16: bool = True,
                   compile_for_hbm: Optional[bool] = None,
                   log=print) -> Tuple[List[Finding], Dict]:
    """Lower + check every legal builder cell; returns (NEW findings
    after the baseline diff, report doc). Illegal cells are asserted
    to refuse with their cell-named ValueError (a cell that stops
    refusing — or a legal cell that starts — is itself a finding:
    the matrix is user-facing API)."""
    import jax

    from fedtorch_tpu.parallel.round_program import (
        cell_name, iter_cells, validate_cell,
    )

    base_fps, base_peaks = load_program_baseline(baseline_path)
    if compile_for_hbm is None:
        # compiling every cell only pays off when there is a recorded
        # watermark to regress against (or one is being written)
        compile_for_hbm = write_baseline or bool(base_peaks)

    t0 = time.time()
    findings: List[Finding] = []
    peaks: Dict[str, float] = {}
    report: Dict = {"schema": "fedtorch_tpu.program_audit/v1",
                    "backend": jax.default_backend(), "cells": {}}

    for source, dispatch, execution in iter_cells():
        cell = cell_name(source, dispatch, execution)
        refusal = _cell_refusal(source, dispatch, execution,
                                validate_cell)
        if refusal is not None:
            report["cells"][cell] = {"legal": False,
                                     "refusal": refusal[:200]}
            log(f"audit: {cell}: refused as expected")
            continue
        variants = [("float32", 0)]
        if include_bf16 and (source, dispatch, execution) in BF16_CELLS:
            variants.append(("bfloat16", 0))
        if (execution == "vmap"
                and len(jax.devices()) >= PODSCALE_SHARDS):
            # the mesh'd twin of every legal vmap cell
            variants.append(("float32", PODSCALE_SHARDS))
        for compute_dtype, shards in variants:
            ev = lower_cell(source, dispatch, execution,
                            compute_dtype=compute_dtype,
                            scan_length=scan_length,
                            client_shards=shards)
            peak = None
            if compile_for_hbm:
                peak = _compiled_peak(ev["lowered"])
                if peak is not None:
                    peaks[ev["cell"]] = peak
            rounds = scan_length if dispatch == "scan" else 1
            cell_findings = audit_cell_evidence(
                ev, compute_dtype=compute_dtype, num_rounds=rounds,
                baseline_peaks=base_peaks, peak=peak)
            findings.extend(cell_findings)
            report["cells"][ev["cell"]] = {
                "legal": True, "program": ev["program"],
                "hlo_bytes": len(ev["text"]),
                "donated_leaves": ev["donated_leaves"],
                "findings": len(cell_findings),
                **({"client_shards": shards} if shards > 1 else {}),
                **({"peak_hbm_bytes": peak} if peak is not None else {}),
            }
            log(f"audit: {ev['cell']}: {len(cell_findings)} finding(s)")

    report["wall_s"] = round(time.time() - t0, 2)
    if write_baseline:
        save_program_baseline(baseline_path, findings, peaks)
        log(f"audit: wrote {len(findings)} fingerprint(s) + "
            f"{len(peaks)} peak(s) to {baseline_path}")
        return [], report
    new, matched = diff_against_baseline(findings, base_fps)
    report["findings_total"] = len(findings)
    report["findings_baselined"] = matched
    report["findings_new"] = len(new)
    return new, report


def _cell_refusal(source, dispatch, execution, validate_cell
                  ) -> Optional[str]:
    """The refusal message the validator raises for this cell on the
    canonical audit config, or None when the cell is legal."""
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.models import define_model

    cfg = _audit_config(source, dispatch, execution)
    alg = make_algorithm(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    try:
        validate_cell(source, dispatch, execution, cfg=cfg,
                      algorithm=alg, model=model, mesh_devices=1,
                      k_online=2, gather_mode="auto", has_val=False)
    except ValueError as e:
        return str(e)
    return None


def _compiled_peak(lowered) -> Optional[float]:
    from fedtorch_tpu.telemetry.costs import cost_summary
    try:
        return cost_summary(lowered.compile()).get("peak_hbm_bytes")
    except Exception:
        return None
