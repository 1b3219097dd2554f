"""Command-line entry point.

Parity with the reference's flag system + entry points (parameters.py
get_args ~90 flags; main.py / main_centered.py): one argparse surface
mapping onto the typed :class:`ExperimentConfig`, a ``--backend`` switch
replacing ``mpirun`` process launch (tpu = all visible TPU devices over
one mesh; cpu = virtual host mesh for debugging, the centered-mode
analog), and the train/validate/checkpoint driver loop
(federated/main.py:56-211).

Usage:
    python -m fedtorch_tpu.cli --federated true --data synthetic \
        --federated_type fedavg --num_comms 20 --num_clients 10
"""
from __future__ import annotations

import argparse
import time

from fedtorch_tpu.config import (
    CLIENT_STORES, PARTICIPATION_MODES,
    CheckpointConfig, DataConfig, ExperimentConfig, FaultConfig,
    FederatedConfig, LRConfig, MeshConfig, ModelConfig, OptimConfig,
    TelemetryConfig, TrainConfig,
)


def str2bool(v) -> bool:
    """parameters.py:263-280."""
    if isinstance(v, bool):
        return v
    if v.lower() in ("yes", "true", "t", "y", "1"):
        return True
    if v.lower() in ("no", "false", "f", "n", "0"):
        return False
    raise argparse.ArgumentTypeError(f"Boolean value expected, got {v!r}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="fedtorch_tpu: TPU-native federated learning")
    # dataset (parameters.py:23-37)
    p.add_argument("-d", "--data", default="cifar10")
    p.add_argument("-p", "--data_dir", default="./data/")
    p.add_argument("--download", type=str2bool, default=False)
    p.add_argument("--partition_data", type=str2bool, default=True)
    p.add_argument("--augment", type=str2bool, default=None,
                   help="train-time flip+crop for image data "
                        "(default: on for the cifar family)")
    p.add_argument("--synthetic_alpha", type=float, default=0.0)
    p.add_argument("--synthetic_beta", type=float, default=0.0)
    p.add_argument("--sensitive_feature", type=int, default=9)
    # federated (parameters.py:40-110)
    p.add_argument("-f", "--federated", type=str2bool, default=False)
    p.add_argument("--num_class_per_client", type=int, default=1)
    p.add_argument("--num_comms", type=int, default=100)
    p.add_argument("--online_client_rate", type=float, default=0.1)
    p.add_argument("--federated_sync_type", default="epoch",
                   choices=["epoch", "local_step"])
    p.add_argument("--num_epochs_per_comm", type=int, default=1)
    p.add_argument("--iid_data", type=str2bool, default=True)
    p.add_argument("--federated_type", default="fedavg")
    p.add_argument("--unbalanced", type=str2bool, default=False)
    p.add_argument("--dirichlet", type=str2bool, default=False)
    p.add_argument("--fed_personal", type=str2bool, default=False)
    p.add_argument("--fed_personal_alpha", type=float, default=0.5)
    p.add_argument("--fed_adaptive_alpha", type=str2bool, default=False)
    p.add_argument("--fed_personal_test", type=str2bool, default=False)
    p.add_argument("--fedadam_beta", type=float, default=0.9)
    p.add_argument("--fedadam_tau", type=float, default=0.1)
    p.add_argument("--quantized", type=str2bool, default=False)
    p.add_argument("--quantized_bits", type=int, default=8)
    p.add_argument("--compressed", type=str2bool, default=False)
    p.add_argument("--compressed_ratio", type=float, default=1.0)
    p.add_argument("--sync_mode", default="sync",
                   choices=("sync", "async"),
                   help="server execution plane: 'sync' (default) "
                        "blocks each round on all k online clients; "
                        "'async' is the FedBuff-style buffered server "
                        "— clients train on possibly-stale snapshots, "
                        "the server commits every --async_buffer_size "
                        "staleness-weighted arrivals, and num_comms "
                        "counts COMMITS (docs/robustness.md "
                        "'Asynchronous federation')")
    p.add_argument("--async_buffer_size", type=int, default=0,
                   help="updates buffered per async commit (FedBuff's "
                        "m); 0 = auto: max(1, k_online // 2)")
    p.add_argument("--async_concurrency", type=int, default=0,
                   help="concurrently-training clients in async mode "
                        "(FedBuff's M_c); 0 = auto: k_online")
    p.add_argument("--staleness_weight", default="poly",
                   choices=("const", "poly", "inv"),
                   help="async staleness damping s(tau) for an update "
                        "tau commits stale: poly=(1+tau)^-exponent "
                        "(FedBuff default), inv=1/(1+tau), const=1; "
                        "normalized to mean 1 per commit")
    p.add_argument("--staleness_exponent", type=float, default=0.5,
                   help="exponent of the 'poly' staleness weight")
    p.add_argument("--snapshot_ring", type=int, default=8,
                   help="async snapshot ring depth: past commit "
                        "versions kept resident for in-flight clients "
                        "(memory: ring x (params + server aux))")
    p.add_argument("--federated_drfa", type=str2bool, default=False)
    p.add_argument("--drfa_gamma", type=float, default=0.1)
    p.add_argument("--perfedavg_beta", type=float, default=0.001)
    p.add_argument("--fedprox_mu", type=float, default=0.002)
    p.add_argument("--perfedme_lambda", type=float, default=15.0)
    p.add_argument("--qffl_q", type=float, default=0.0)
    # model (parameters.py:113-115, 180-194)
    p.add_argument("-a", "--arch", default="mlp")
    p.add_argument("--norm", default="bn", choices=["bn", "gn"])
    p.add_argument("--drop_rate", type=float, default=0.0)
    p.add_argument("--densenet_growth_rate", type=int, default=12)
    p.add_argument("--densenet_bc_mode", type=str2bool, default=False)
    p.add_argument("--densenet_compression", type=float, default=0.5)
    p.add_argument("--wideresnet_widen_factor", type=int, default=4)
    p.add_argument("--mlp_num_layers", type=int, default=2)
    p.add_argument("--mlp_hidden_size", type=int, default=500)
    p.add_argument("--rnn_seq_len", type=int, default=50)
    p.add_argument("--rnn_hidden_size", type=int, default=50)
    p.add_argument("--vocab_size", type=int, default=86)
    p.add_argument("--moe_experts", type=int, default=0,
                   help="transformer arch: >0 swaps block MLPs for a "
                        "Switch-MoE with this many experts. With "
                        "--moe_capacity_factor 0 dispatch is exact but "
                        "costs E x the dense MLP FLOPs")
    p.add_argument("--moe_capacity_factor", type=float, default=0.0,
                   help="0 = exact dense MoE dispatch (E x FLOPs); >0 "
                        "= sparse Switch dispatch, per-expert capacity "
                        "ceil(cf*tokens/E), cf x FLOPs, over-capacity "
                        "tokens drop to the residual (try 1.25)")
    p.add_argument("--moe_aux_weight", type=float, default=0.0,
                   help="Switch load-balance aux-loss weight (0.01 in "
                        "the paper); 0 disables and the gate can "
                        "collapse onto one expert")
    p.add_argument("--model_spec", default=None,
                   help="arch hybrid_lm: a JSON file with the keys of "
                        "a public config.json (hidden_size, "
                        "intermediate_size, num_hidden_layers, "
                        "layer_types, num_attention_heads, "
                        "vocab_size, rms_norm_eps; linear_* where a "
                        "layer is a linear_attention one; optional "
                        "model_type, rope_theta, total_ut_steps) the "
                        "model's shape is read from")
    p.add_argument("--attention", default="auto",
                   choices=("auto", "dense", "flash"),
                   help="transformer attention backend: 'flash' = fused "
                        "online-softmax pallas kernel on TPU (exact; "
                        "dense fallback off-TPU); 'auto' (default) "
                        "picks flash at sequence lengths T >= 4096 "
                        "(ops/attention_dispatch.py)")
    # training scheme (parameters.py:118-141)
    p.add_argument("--stop_criteria", default="epoch")
    p.add_argument("--num_epochs", type=int, default=None)
    p.add_argument("--num_iterations", type=int, default=None)
    p.add_argument("--local_step", type=int, default=1)
    p.add_argument("--local_step_warmup_type", default=None)
    p.add_argument("--local_step_warmup_period", type=int, default=None)
    p.add_argument("--local_step_warmup_per_interval", type=str2bool,
                   default=False)
    p.add_argument("--turn_on_local_step_from", type=int, default=None)
    p.add_argument("--turn_off_local_step_from", type=int, default=None)
    p.add_argument("--avg_model", type=str2bool, default=True)
    p.add_argument("--reshuffle_per_epoch", type=str2bool, default=False)
    p.add_argument("-b", "--batch_size", type=int, default=50)
    p.add_argument("--data_plane", default="device",
                   choices=("device", "stream"),
                   help="federated data plane: 'device' keeps every "
                        "client's rows resident in device memory "
                        "(population capped by HBM); 'stream' keeps "
                        "the client store on the host and prefetches "
                        "each round's packed online-client rows one "
                        "round ahead, overlapping the transfer with "
                        "the previous round's compute "
                        "(docs/performance.md 'Streaming data plane')")
    p.add_argument("--data_store", default="ram",
                   choices=CLIENT_STORES,
                   help="client-store backend on the stream plane: "
                        "'ram' (default) holds the [C, n_max, ...] "
                        "population in host memory; 'mmap' memory-maps "
                        "a sharded on-disk store built by "
                        "save_client_store — host residency is "
                        "O(touched rows), enabling million-client "
                        "populations (docs/performance.md 'The "
                        "million-client store')")
    p.add_argument("--data_store_dir", default="",
                   help="directory holding the mmap store's "
                        "manifest.json + shard files (required with "
                        "--data_store mmap)")
    p.add_argument("--participation_mode", default="perm",
                   choices=PARTICIPATION_MODES,
                   help="per-round client sampling: 'perm' (default, "
                        "legacy-bitwise) draws a [C] random score "
                        "vector per selection; 'sparse' draws O(k) "
                        "without-replacement ids and never "
                        "materializes a [C] array — required reading "
                        "at million-client populations "
                        "(docs/performance.md)")
    p.add_argument("--growing_batch_size", type=str2bool, default=False)
    p.add_argument("--base_batch_size", type=int, default=None)
    p.add_argument("--max_batch_size", type=int, default=0)
    # learning rate (parameters.py:144-166)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--lr_schedule_scheme", default=None)
    p.add_argument("--lr_change_epochs", default=None)
    p.add_argument("--lr_fields", default=None)
    p.add_argument("--lr_scale_indicators", default=None)
    p.add_argument("--lr_scaleup", type=str2bool, default=False)
    p.add_argument("--lr_scaleup_type", default="linear")
    p.add_argument("--lr_scale_at_sync", type=float, default=1.0)
    p.add_argument("--lr_warmup", type=str2bool, default=False)
    p.add_argument("--lr_warmup_epochs", type=int, default=5)
    p.add_argument("--lr_decay", type=float, default=10.0)
    p.add_argument("--lr_onecycle_low", type=float, default=0.15)
    p.add_argument("--lr_onecycle_high", type=float, default=3.0)
    p.add_argument("--lr_onecycle_extra_low", type=float, default=0.0015)
    p.add_argument("--lr_onecycle_num_epoch", type=int, default=46)
    p.add_argument("--lr_gamma", type=float, default=None)
    p.add_argument("--lr_mu", type=float, default=None)
    p.add_argument("--lr_alpha", type=float, default=None)
    # optimizer (parameters.py:168-183)
    p.add_argument("--optimizer", default="sgd")
    p.add_argument("--in_momentum", type=str2bool, default=False)
    p.add_argument("--in_momentum_factor", type=float, default=0.9)
    p.add_argument("--out_momentum", type=str2bool, default=False)
    p.add_argument("--out_momentum_factor", type=float, default=None)
    p.add_argument("--use_nesterov", type=str2bool, default=False)
    p.add_argument("--weight_decay", type=float, default=5e-4)
    p.add_argument("--correct_wd", type=str2bool, default=False)
    p.add_argument("--wd_skip_norm_bias", type=str2bool, default=False,
                   help="exclude norm scale/shift and bias params from "
                        "weight decay (standard practice); default "
                        "False = the reference's uniform decay, which "
                        "parity runs must keep")
    # misc / checkpoint (parameters.py:196-222)
    p.add_argument("--manual_seed", type=int, default=6)
    p.add_argument("--per_class_acc", type=str2bool, default=False)
    p.add_argument("--evaluate", "-e", type=str2bool, default=False)
    p.add_argument("--eval_freq", type=int, default=1)
    p.add_argument("--summary_freq", type=int, default=10)
    p.add_argument("--debug", type=str2bool, default=True)
    p.add_argument("--resume", default=None)
    p.add_argument("--checkpoint_index", default=None)
    p.add_argument("-c", "--checkpoint", default="./checkpoint/")
    p.add_argument("--run_dir", default=None,
                   help="use this exact directory for checkpoints/logs "
                        "instead of a hyperparam+timestamp subfolder of "
                        "--checkpoint; required for elastic restarts "
                        "(run_elastic/supervise relaunch with "
                        "--resume <this dir>)")
    p.add_argument("--save_all_models", type=str2bool, default=False)
    p.add_argument("--save_some_models", default="1,29,59")
    p.add_argument("--checkpoint_keep_last_n", type=int, default=0,
                   help="garbage-collect all but the newest N per-round "
                        "checkpoint_r{N}.ckpt keeps (0 = keep all; "
                        "model_best/checkpoint.ckpt never collected)")
    p.add_argument("--async_checkpoint", action="store_true",
                   help="write checkpoints from a background thread "
                        "(atomic) so rounds never block on disk")
    p.add_argument("--check_model_at_sync", type=str2bool, default=False)
    p.add_argument("--track_model_aggregation", type=str2bool,
                   default=False)
    p.add_argument("--log_dir", default="./logdir/")
    p.add_argument("--experiment", default=None)
    # robustness: chaos injection / update guards / round supervisor
    # (docs/robustness.md; no reference analog — it is fail-stop)
    p.add_argument("--fault_client_drop_rate", type=float, default=0.0,
                   help="per-round probability an online client crashes "
                        "mid-round (masked out of aggregation, weights "
                        "renormalized over survivors)")
    p.add_argument("--fault_straggler_rate", type=float, default=0.0,
                   help="per-round probability an online client is a "
                        "straggler (completes only a fraction of its "
                        "local steps)")
    p.add_argument("--fault_straggler_step_frac", type=float, default=0.5,
                   help="fraction of the step budget a straggler "
                        "completes before missing the deadline")
    p.add_argument("--fault_nan_inject_rate", type=float, default=0.0,
                   help="per-round probability an online client uploads "
                        "a NaN-poisoned delta (exercises the guards)")
    p.add_argument("--fault_byzantine_rate", type=float, default=0.0,
                   help="per-round probability an online client is an "
                        "ADVERSARY: its upload is replaced by a crafted "
                        "finite vector that passes the benign-fault "
                        "guards (the robust_agg layer is the defense)")
    p.add_argument("--fault_byzantine_mode", default="sign_flip",
                   choices=("sign_flip", "scale", "zero", "gauss",
                            "collude"),
                   help="attack shape: sign_flip=-scale*delta, "
                        "scale=norm inflation, zero=free-rider, "
                        "gauss=pure noise, collude=all byzantine "
                        "clients submit the identical "
                        "-scale*(honest mean) update")
    p.add_argument("--fault_byzantine_scale", type=float, default=1.0,
                   help="attack magnitude multiplier")
    p.add_argument("--robust_agg", default="mean",
                   choices=("mean", "median", "trimmed_mean", "krum",
                            "multikrum", "norm_bound"),
                   help="aggregation rule at the round/commit seam "
                        "(robustness/aggregators.py): 'mean' (default) "
                        "is the pre-robust weighted sum, bitwise-"
                        "identical; median/trimmed_mean are "
                        "coordinate-wise (Yin et al. 2018), "
                        "krum/multikrum pairwise-distance selection "
                        "(Blanchard et al. 2017), norm_bound centered "
                        "clipping with a server momentum "
                        "(Karimireddy et al. 2021). Composes after "
                        "guards/chaos and async staleness weights on "
                        "BOTH federation planes")
    p.add_argument("--robust_trim_frac", type=float, default=0.1,
                   help="trimmed_mean's per-end trim fraction and "
                        "krum's assumed byzantine fraction")
    p.add_argument("--robust_norm_tau", type=float, default=1.5,
                   help="norm_bound clip radius as a multiple of the "
                        "median distance-to-momentum (1.5: adversaries "
                        "clamp hard, clustered honest updates barely)")
    p.add_argument("--guard_updates", type=str2bool, default=False,
                   help="screen client deltas before aggregation: "
                        "reject non-finite, reject/clip norm-exploded")
    p.add_argument("--guard_norm_multiplier", type=float, default=10.0,
                   help="norm threshold as a multiple of the round's "
                        "median surviving delta norm")
    p.add_argument("--guard_mode", default="reject",
                   choices=("reject", "clip"))
    p.add_argument("--supervisor", type=str2bool, default=False,
                   help="wrap the round loop with divergence detection, "
                        "snapshot rollback, retry with backoff, and "
                        "round skipping (docs/robustness.md)")
    p.add_argument("--supervisor_loss_blowup", type=float, default=0.0,
                   help=">0: mean online loss above this multiple of "
                        "the running loss EMA counts as divergence")
    p.add_argument("--supervisor_max_retries", type=int, default=2)
    p.add_argument("--supervisor_backoff_base", type=float, default=0.5)
    p.add_argument("--host_fault_seams", default="",
                   help="comma-separated host-plane fault seams to arm "
                        "(robustness/host_chaos.py): stream.gather, "
                        "stream.delay, stream.h2d, ckpt.write, "
                        "ckpt.torn, telemetry.write, native.load. "
                        "Faults fire deterministically from "
                        "--host_fault_seed, so every drill replays "
                        "(docs/robustness.md 'Host plane')")
    p.add_argument("--host_fault_rate", type=float, default=0.25,
                   help="per-check fire probability at each armed "
                        "host seam")
    p.add_argument("--host_fault_seed", type=int, default=0,
                   help="seed of the pure-hash fault schedule")
    p.add_argument("--host_fault_delay_s", type=float, default=0.02,
                   help="stall injected per fire at the stream.delay "
                        "seam (seconds)")
    p.add_argument("--host_fault_max", type=int, default=0,
                   help=">0 caps total fires per seam (e.g. "
                        "host_retry_max+1 at rate 1.0 kills the stream "
                        "producer exactly once for the rebuild drill); "
                        "0 = uncapped")
    p.add_argument("--host_retry_max", type=int, default=3,
                   help="bounded retry budget at each host seam "
                        "(stream gather/H2D, checkpoint writes) and "
                        "the producer-rebuild budget per feed pop "
                        "(robustness/host_recovery.py)")
    p.add_argument("--host_retry_backoff_s", type=float, default=0.05,
                   help="first host-seam retry delay; doubles per "
                        "attempt (capped at 2s)")
    p.add_argument("--watchdog_timeout_s", type=float, default=0.0,
                   help=">0 arms the stall watchdog: if no round "
                        "completes within this many seconds (a dead "
                        "peer blocking a DCN collective), dump thread "
                        "stacks to the run log and exit with the "
                        "restartable code 75 so the restart harness "
                        "cycles the job (docs/robustness.md)")
    # deployment-realism availability plane + round lifecycle
    # (robustness/availability.py; docs/robustness.md "Deployment
    # realism")
    p.add_argument("--avail_model", default="default",
                   choices=("default", "trace"),
                   help="client availability model driving async "
                        "arrival delays and the sync round lifecycle: "
                        "'default' reproduces the legacy straggler-"
                        "knob draws bitwise; 'trace' adds FedScale-"
                        "style device speed classes and diurnal on/off "
                        "curves from an in-tree synthetic trace")
    p.add_argument("--avail_dropout_rate", type=float, default=0.0,
                   help="per-dispatch probability a client drops "
                        "mid-round (async: arrival discarded and slot "
                        "re-dispatched; sync: local state rolled back "
                        "and update masked)")
    p.add_argument("--avail_diurnal_period", type=int, default=0,
                   help="trace model only: rounds per diurnal cycle "
                        "(0 = flat availability)")
    p.add_argument("--over_select_frac", type=float, default=1.0,
                   help=">1 over-selects ceil(frac*k) clients per sync "
                        "round and closes the round on the first k "
                        "arrivals; late survivors are deadline-masked "
                        "through the accept seam")
    p.add_argument("--avail_quorum_frac", type=float, default=0.0,
                   help=">0: a sync round whose accepted cohort falls "
                        "below ceil(frac*k) is sub-quorum — see "
                        "--avail_quorum_action")
    p.add_argument("--avail_quorum_action", default="degrade",
                   choices=("degrade", "abort"),
                   help="sub-quorum handling: 'degrade' commits the "
                        "renormalized partial cohort (counted + "
                        "evented); 'abort' escalates to the supervisor "
                        "retry/skip path (requires --supervisor)")
    p.add_argument("--dp_noise_multiplier", type=float, default=0.0,
                   help="> 0 arms DP-FedAvg server aggregation: "
                        "per-client L2 clip to --dp_clip_norm then "
                        "Gaussian noise z*clip/k on the weighted "
                        "estimate (0 = off, program byte-identical)")
    p.add_argument("--dp_clip_norm", type=float, default=1.0,
                   help="per-client L2 clip radius for the DP stage")
    p.add_argument("--dp_epsilon_budget", type=float, default=0.0,
                   help="> 0 caps the RDP-accounted epsilon spend at "
                        "--dp_delta; exhaustion handled per "
                        "--dp_budget_action (0 = unlimited; spend is "
                        "still accounted and logged)")
    p.add_argument("--dp_delta", type=float, default=1e-5,
                   help="target delta for the (eps, delta) accounting")
    p.add_argument("--dp_budget_action", default="stop",
                   choices=("stop", "degrade"),
                   help="epsilon-budget exhaustion: 'stop' ends the "
                        "run cleanly at the last affordable round; "
                        "'degrade' continues noise-free (counted + "
                        "evented, health intent 'degraded')")
    # device / mesh (replaces parameters.py:225-236 MPI block)
    p.add_argument("--backend", default=None,
                   help="jax platform: tpu|cpu|None(auto)")
    p.add_argument("--num_devices", type=int, default=None)
    p.add_argument("--num_workers", "-j", "--world_size", type=int,
                   default=10, dest="num_workers",
                   help="number of clients/workers (MPI world size)")
    p.add_argument("--coordinator_address", default=None,
                   help="multi-host DCN coordinator (host:port)")
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    p.add_argument("--compute_dtype", default="float32",
                   choices=("float32", "bfloat16"),
                   help="matmul/conv compute dtype (params stay f32); "
                        "bfloat16 feeds the MXU at full rate")
    p.add_argument("--remat", action="store_true",
                   help="per-block rematerialization for resnet/"
                        "transformer: ~1.33x FLOPs for depth-independent "
                        "activation memory")
    p.add_argument("--client_fusion", default="auto",
                   choices=("auto", "vmap", "sequential"),
                   help="how the round program runs the cohort's k "
                        "clients: 'vmap' stacks them; 'sequential' "
                        "runs one after another into a running sum, "
                        "for a model too large to stack k times; "
                        "'auto' is 'vmap' (docs/performance.md)")
    p.add_argument("--client_shards", type=int, default=0,
                   help="pod-scale client-axis sharding: shard the k "
                        "online clients over this many device groups "
                        "(power of two <= 64 dividing both the device "
                        "count and k) with exactly one cross-shard "
                        "all-reduce at the aggregation seam; 0 = off "
                        "(legacy program), 1 = the unsharded bitwise "
                        "twin (docs/performance.md 'Pod-scale round "
                        "programs')")
    p.add_argument("--allow_train_as_test", type=str2bool, default=False,
                   help="permit dataset loaders with a missing test "
                        "split (EMNIST mirrors) to substitute a slice "
                        "of TRAIN data as the test set; off by default "
                        "because it silently reports train accuracy "
                        "as test accuracy")
    # observability (fedtorch_tpu.telemetry, docs/observability.md)
    p.add_argument("--telemetry", default="default",
                   choices=("off", "default", "debug"),
                   help="run telemetry: 'default' writes schema-"
                        "versioned metrics.jsonl/events.jsonl, a "
                        "Perfetto-loadable trace.json of host spans, "
                        "and the atomically-replaced health.json to "
                        "the run dir (zero added device syncs); "
                        "'debug' re-exports the trace every 25 rounds; "
                        "'off' disables everything "
                        "(docs/observability.md)")
    p.add_argument("--cost_capture_scan_rounds", type=int, default=0,
                   help="> 0 additionally AOT-lowers the scan-of-R "
                        "round-program twin for the active data "
                        "source into program_costs.json at the "
                        "one-shot cost capture (rounds_scan[R] on "
                        "the device plane, rounds_stream_scan[R] — "
                        "the scanned streamed program — on the "
                        "stream plane); 0 captures the per-round "
                        "programs only. Ignored (with a logged note) "
                        "under --sync_mode async, whose commit plane "
                        "refuses the scan dispatch")
    p.add_argument("--cohort_stats", type=str2bool, default=False,
                   help="federation-plane cohort statistics "
                        "(docs/observability.md 'Federation plane'): "
                        "the aggregation seam additionally emits "
                        "per-client accept/selection masks, the "
                        "robust rule's suspicion scores, per-job "
                        "staleness, update-norm quantiles and the "
                        "cosine-dispersion heterogeneity gauge — all "
                        "riding the round loop's one batched fetch "
                        "into per-round gauges and the per-client "
                        "client_ledger.json. Off (default) the round/"
                        "commit program is byte-identical to the "
                        "stats-free engine; on, it traces once and "
                        "trajectories stay bitwise-identical")
    p.add_argument("--ledger_sketch_budget", type=int, default=65536,
                   help="population threshold/budget of the per-"
                        "client ledger: dense numpy counters at "
                        "num_clients <= budget, count-min "
                        "participation sketch + suspicion top-K "
                        "above it — ledger memory stays "
                        "O(min(C, budget)) at C >= 1e6")
    p.add_argument("--anomaly_zscore", type=float, default=6.0,
                   help="EWMA z-score threshold of the observe-only "
                        "anomaly detector over the metrics rows "
                        "(loss, cohort dispersion, guard-reject "
                        "rate, staleness) — emits anomaly.detected "
                        "events, never drives control flow; 0 "
                        "disables")
    return p


def args_to_config(args) -> ExperimentConfig:
    cfg = ExperimentConfig(
        data=DataConfig(
            dataset=args.data, data_dir=args.data_dir,
            partition_data=args.partition_data, iid=args.iid_data,
            num_class_per_client=args.num_class_per_client,
            unbalanced=args.unbalanced, dirichlet=args.dirichlet,
            synthetic_alpha=args.synthetic_alpha,
            synthetic_beta=args.synthetic_beta,
            sensitive_feature=args.sensitive_feature,
            data_plane=args.data_plane,
            store=args.data_store,
            store_dir=args.data_store_dir,
            batch_size=args.batch_size,
            growing_batch_size=args.growing_batch_size,
            base_batch_size=args.base_batch_size,
            max_batch_size=args.max_batch_size,
            reshuffle_per_epoch=args.reshuffle_per_epoch,
            augment=args.augment,
            allow_train_as_test=args.allow_train_as_test),
        federated=FederatedConfig(
            federated=args.federated, num_clients=args.num_workers,
            num_comms=args.num_comms,
            online_client_rate=args.online_client_rate,
            sync_type=args.federated_sync_type,
            num_epochs_per_comm=args.num_epochs_per_comm,
            sync_mode=args.sync_mode,
            participation_mode=args.participation_mode,
            async_buffer_size=args.async_buffer_size,
            async_concurrency=args.async_concurrency,
            staleness_weight=args.staleness_weight,
            staleness_exponent=args.staleness_exponent,
            snapshot_ring=args.snapshot_ring,
            algorithm=args.federated_type, personal=args.fed_personal,
            personal_alpha=args.fed_personal_alpha,
            adaptive_alpha=args.fed_adaptive_alpha,
            personal_test=args.fed_personal_test,
            fedadam_beta=args.fedadam_beta, fedadam_tau=args.fedadam_tau,
            quantized=args.quantized, quantized_bits=args.quantized_bits,
            compressed=args.compressed,
            compressed_ratio=args.compressed_ratio,
            drfa=args.federated_drfa, drfa_gamma=args.drfa_gamma,
            perfedavg_beta=args.perfedavg_beta,
            fedprox_mu=args.fedprox_mu,
            perfedme_lambda=args.perfedme_lambda, qffl_q=args.qffl_q),
        model=ModelConfig(
            arch=args.arch, norm=args.norm, drop_rate=args.drop_rate,
            densenet_growth_rate=args.densenet_growth_rate,
            densenet_bc_mode=args.densenet_bc_mode,
            densenet_compression=args.densenet_compression,
            wideresnet_widen_factor=args.wideresnet_widen_factor,
            mlp_num_layers=args.mlp_num_layers,
            mlp_hidden_size=args.mlp_hidden_size,
            rnn_seq_len=args.rnn_seq_len,
            rnn_hidden_size=args.rnn_hidden_size,
            vocab_size=args.vocab_size,
            moe_experts=args.moe_experts,
            moe_capacity_factor=args.moe_capacity_factor,
            moe_aux_weight=args.moe_aux_weight,
            attention=args.attention,
            spec_file=args.model_spec),
        optim=OptimConfig(
            optimizer=args.optimizer, lr=args.lr,
            in_momentum=args.in_momentum,
            in_momentum_factor=args.in_momentum_factor,
            out_momentum=args.out_momentum,
            out_momentum_factor=args.out_momentum_factor,
            use_nesterov=args.use_nesterov,
            weight_decay=args.weight_decay, correct_wd=args.correct_wd,
            wd_skip_norm_bias=args.wd_skip_norm_bias,
            lr_scale_at_sync=args.lr_scale_at_sync),
        lr_schedule=LRConfig(
            schedule_scheme=args.lr_schedule_scheme,
            lr_change_epochs=args.lr_change_epochs,
            lr_fields=args.lr_fields,
            lr_scale_indicators=args.lr_scale_indicators,
            scaleup=args.lr_scaleup, scaleup_type=args.lr_scaleup_type,
            warmup=args.lr_warmup, warmup_epochs=args.lr_warmup_epochs,
            decay=args.lr_decay, onecycle_low=args.lr_onecycle_low,
            onecycle_high=args.lr_onecycle_high,
            onecycle_extra_low=args.lr_onecycle_extra_low,
            onecycle_num_epoch=args.lr_onecycle_num_epoch,
            gamma=args.lr_gamma, mu=args.lr_mu, alpha=args.lr_alpha),
        train=TrainConfig(
            stop_criteria=args.stop_criteria, num_epochs=args.num_epochs,
            num_iterations=args.num_iterations,
            local_step=args.local_step,
            local_step_warmup_type=args.local_step_warmup_type,
            local_step_warmup_period=args.local_step_warmup_period,
            local_step_warmup_per_interval=(
                args.local_step_warmup_per_interval),
            turn_on_local_step_from=args.turn_on_local_step_from,
            turn_off_local_step_from=args.turn_off_local_step_from,
            avg_model=args.avg_model, manual_seed=args.manual_seed,
            evaluate=args.evaluate, eval_freq=args.eval_freq,
            summary_freq=args.summary_freq,
            per_class_acc=args.per_class_acc),
        checkpoint=CheckpointConfig(
            checkpoint_dir=args.checkpoint, run_dir=args.run_dir,
            resume=args.resume,
            checkpoint_index=args.checkpoint_index,
            save_all_models=args.save_all_models,
            save_some_models=args.save_some_models,
            keep_last_n=args.checkpoint_keep_last_n,
            async_save=args.async_checkpoint,
            log_dir=args.log_dir, debug=args.debug,
            check_model_at_sync=args.check_model_at_sync,
            track_model_aggregation=args.track_model_aggregation),
        mesh=MeshConfig(
            backend=args.backend, num_devices=args.num_devices,
            coordinator_address=args.coordinator_address,
            num_processes=args.num_processes, process_id=args.process_id,
            compute_dtype=args.compute_dtype,
            remat=args.remat,
            client_fusion=args.client_fusion,
            client_shards=args.client_shards),
        telemetry=TelemetryConfig(
            level=args.telemetry,
            cost_capture_scan_rounds=args.cost_capture_scan_rounds,
            cohort_stats=args.cohort_stats,
            ledger_sketch_budget=args.ledger_sketch_budget,
            anomaly_zscore=args.anomaly_zscore),
        fault=FaultConfig(
            client_drop_rate=args.fault_client_drop_rate,
            straggler_rate=args.fault_straggler_rate,
            straggler_step_frac=args.fault_straggler_step_frac,
            nan_inject_rate=args.fault_nan_inject_rate,
            byzantine_rate=args.fault_byzantine_rate,
            byzantine_mode=args.fault_byzantine_mode,
            byzantine_scale=args.fault_byzantine_scale,
            robust_agg=args.robust_agg,
            robust_trim_frac=args.robust_trim_frac,
            robust_norm_tau=args.robust_norm_tau,
            guard_updates=args.guard_updates,
            guard_norm_multiplier=args.guard_norm_multiplier,
            guard_mode=args.guard_mode,
            supervisor=args.supervisor,
            loss_blowup_factor=args.supervisor_loss_blowup,
            max_retries=args.supervisor_max_retries,
            backoff_base_s=args.supervisor_backoff_base,
            host_fault_seams=args.host_fault_seams,
            host_fault_rate=args.host_fault_rate,
            host_fault_seed=args.host_fault_seed,
            host_fault_delay_s=args.host_fault_delay_s,
            host_fault_max=args.host_fault_max,
            host_retry_max=args.host_retry_max,
            host_retry_backoff_s=args.host_retry_backoff_s,
            watchdog_timeout_s=args.watchdog_timeout_s,
            avail_model=args.avail_model,
            avail_dropout_rate=args.avail_dropout_rate,
            avail_diurnal_period=args.avail_diurnal_period,
            over_select_frac=args.over_select_frac,
            avail_quorum_frac=args.avail_quorum_frac,
            avail_quorum_action=args.avail_quorum_action,
            dp_noise_multiplier=args.dp_noise_multiplier,
            dp_clip_norm=args.dp_clip_norm,
            dp_epsilon_budget=args.dp_epsilon_budget,
            dp_delta=args.dp_delta,
            dp_budget_action=args.dp_budget_action),
        experiment=args.experiment,
    )
    return cfg.finalize()


def run_experiment(cfg: ExperimentConfig,
                   download: bool = False,
                   round_callback=None) -> dict:
    """The driver loop (main.py dispatch + federated/main.py:56-211).

    ``round_callback(r, trainer, server, clients, metrics)`` (optional)
    fires after every completed federated round — the hook the
    preemption/kill-drill harness uses to fingerprint rounds.

    Process lifecycle (docs/robustness.md "Process lifecycle"):
    SIGTERM/SIGINT/SIGUSR1 request a drain — the loop finishes the
    round in flight, agrees on the stop across hosts, writes a final
    checkpoint, flushes the async writer, and the result carries
    ``preempted=True`` (:func:`main` converts that into the restartable
    exit code 75). ``fault.watchdog_timeout_s > 0`` additionally arms a
    stall watchdog that converts a wedged pod into the same exit code.
    """
    import jax
    import jax.numpy as jnp

    from fedtorch_tpu.utils import enable_compile_cache
    enable_compile_cache()

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import (
        FederatedTrainer, build_local_sgd, evaluate_personal,
        init_multihost,
    )
    from fedtorch_tpu.parallel.evaluate import evaluate_to_host
    from fedtorch_tpu.utils import (
        PhaseTimer, RunLogger, aggregation_tracking, init_checkpoint_dir,
        maybe_resume, model_norms, save_checkpoint,
    )

    if cfg.mesh.backend:
        jax.config.update("jax_platforms", cfg.mesh.backend)
    init_multihost(cfg.mesh)

    ckpt_dir = init_checkpoint_dir(cfg)
    logger = RunLogger(ckpt_dir, debug=cfg.checkpoint.debug)
    logger.log_args(cfg)
    logger.log(f"devices: {jax.devices()}")
    timer = PhaseTimer()

    # unified run telemetry (docs/observability.md): structured
    # metrics/events + host spans + machine-readable health, written to
    # the run dir. Host-only: every value it records is either a host
    # counter or comes from the loop's ONE batched scalar fetch below —
    # zero added device syncs, traced programs untouched. Every span
    # also opens a profiler annotation, so a profile taken of this
    # process holds the host spans beside the device's operations.
    from fedtorch_tpu.telemetry import Telemetry
    from fedtorch_tpu.utils.tracing import CompileSpans
    tel = Telemetry(
        ckpt_dir, level=cfg.telemetry.level,
        process_index=jax.process_index(),
        run_meta={
            "algorithm": cfg.effective_algorithm,
            "dataset": cfg.data.dataset, "arch": cfg.model.arch,
            "sync_mode": cfg.federated.sync_mode,
            "data_plane": cfg.data.data_plane,
            "num_clients": cfg.federated.num_clients,
            "num_comms": cfg.federated.num_comms,
            "experiment": cfg.experiment,
        },
        max_span_events=cfg.telemetry.max_span_events,
        annotate=jax.profiler.TraceAnnotation)
    tel.install()
    # JAX's own trace / lower / compile reports as spans, for every
    # program of the process (utils/tracing.py)
    compile_spans = CompileSpans(tel.spans).install() \
        if tel.spans is not None else None
    tel.health_update("starting")

    # host-plane chaos + self-healing (docs/robustness.md "Host
    # plane"): the recovery ledger is ALWAYS installed — real host
    # faults (a full disk, a gather hiccup) retry and count whether or
    # not a drill is armed; the seeded injector only when
    # --host_fault_seams named seams. Both are host-only: no traced
    # program changes, no device syncs.
    from fedtorch_tpu.robustness import host_chaos, host_recovery
    recovery = host_recovery.HostRecovery(
        policy=host_recovery.RetryPolicy(
            max_retries=cfg.fault.host_retry_max,
            backoff_base_s=cfg.fault.host_retry_backoff_s)).install()
    injector = host_chaos.HostFaultInjector.from_config(cfg.fault)
    if injector is not None:
        injector.install()
        logger.log("host chaos armed: seams="
                   f"{','.join(sorted(injector.seams))} "
                   f"rate={injector.rate} seed={injector.seed}")

    def _uninstall_host_plane():
        # paired with every tel.close(): the active injector/ledger
        # must not leak past this run into a library caller's next one
        if injector is not None:
            injector.uninstall()
        recovery.uninstall()
        if compile_spans is not None:
            compile_spans.close()

    # everything from data build through trainer/handler
    # construction can raise (dataset IO, the async/stream
    # gate matrix, resume incompatibility): the active
    # telemetry must not leak past this run into a library
    # caller's next one
    try:
        timer.start("data")
        with tel.span("data.build"):
            fed_data = build_federated_data(cfg, download=download)
            model = define_model(cfg, batch_size=cfg.data.batch_size)
        timer.stop("data")

        rng = jax.random.key(cfg.train.manual_seed)

        if not cfg.federated.federated:
            # local-SGD mode: flatten the per-worker shards back into one
            # training set and IID-repartition across workers
            import numpy as np
            try:
                splits_x = np.asarray(fed_data.train.x).reshape(
                    (-1,) + fed_data.train.x.shape[2:])
                splits_y = np.asarray(fed_data.train.y).reshape(-1)
                trainer = build_local_sgd(cfg, model, splits_x, splits_y)
                server, clients, history = trainer.fit(rng)
                res = evaluate_to_host(model, server.params,
                                       fed_data.test_x, fed_data.test_y)
                logger.log_val(len(history), "test", float(res.loss),
                               float(res.top1), float(res.top5))
                tel.health_update("complete", round_idx=len(history))
            finally:
                _uninstall_host_plane()
                tel.close()
            return {"test_top1": float(res.top1), "rounds": len(history)}

        algorithm = make_algorithm(cfg)
        with tel.span("trainer.build"):
            if cfg.federated.sync_mode == "async":
                # the async commit plane (docs/robustness.md
                # "Asynchronous federation"): run_round executes one
                # COMMIT and server.round counts commit versions, so the
                # loop below — checkpointing, eval cadence, preemption
                # drain, supervisor — runs unchanged
                from fedtorch_tpu.async_plane import AsyncFederatedTrainer
                trainer = AsyncFederatedTrainer(cfg, model, algorithm,
                                                fed_data.train,
                                                val_data=fed_data.val)
            else:
                trainer = FederatedTrainer(cfg, model, algorithm,
                                           fed_data.train,
                                           val_data=fed_data.val)
        with tel.span("state.init"):
            server, clients = trainer.init_state(rng)
        server, clients, best_prec1, resumed = maybe_resume(
            cfg.checkpoint.resume, server, clients, cfg,
            cfg.checkpoint.checkpoint_index)
        if resumed:
            logger.log("resumed from round "
                       f"{int(jax.device_get(server.round))}")

        save_rounds = tuple(
            int(x) for x in cfg.checkpoint.save_some_models.split(","))
        async_ckpt = None
        if cfg.checkpoint.async_save:
            from fedtorch_tpu.utils import AsyncCheckpointer
            async_ckpt = AsyncCheckpointer()
        saver = async_ckpt.save if async_ckpt is not None else save_checkpoint
        last_saved_round = None
        lost_at_save = 0
        supervisor = None
        run_round = trainer.run_round
        if cfg.fault.supervisor:
            from fedtorch_tpu.robustness import RoundSupervisor
            supervisor = RoundSupervisor(trainer, checkpoint_dir=ckpt_dir,
                                         logger=logger)
            run_round = supervisor.run_round
        # process lifecycle: signal-driven drain + stall watchdog
        # (robustness/preemption.py, robustness/watchdog.py). The stop
        # decision is SPMD-agreed via the per-round scalar fetch; the
        # watchdog is host-only and off by default (watchdog_timeout_s=0).
        from fedtorch_tpu.robustness import PreemptionHandler, StallWatchdog
        from fedtorch_tpu.robustness.guards import (
            all_rejected_scalars as _all_rejected,
        )
        preempt = PreemptionHandler(logger=logger)
        preempt.install()
        trainer.attach_stop_signal(lambda: preempt.stop_requested)
        # NOTE for operators: the timeout must comfortably exceed the
        # worst-case compile + round + eval + checkpoint time — the first
        # round pays XLA compilation under the same clock.
        watchdog = StallWatchdog(cfg.fault.watchdog_timeout_s, logger=logger)
        watchdog.start()
        # device-side cost capture (telemetry.costs,
        # docs/observability.md "Device-side"): process 0 AOT-lowers
        # uninstrumented twins of the round/commit + eval programs ONCE
        # after the first round (on the TPU a real second compile of
        # the round in a cold process — telemetry/costs.py docstring)
        # and writes program_costs.json; afterwards every metrics row
        # carries the measured-MFU and HBM-watermark gauges computed
        # from host state alone — the traced programs never change
        # (HLO byte-identical, sentinel holds; pinned in
        # tests/test_device_observability.py)
        cost_capture = None
        if cfg.telemetry.cost_capture_scan_rounds > 0 \
                and cfg.federated.sync_mode == "async":
            # the async trainer's lowered_cost_programs ignores
            # num_scan_rounds (its commit plane refuses the scan
            # dispatch) — say so instead of silently dropping the flag
            logger.log(
                "cost capture: --cost_capture_scan_rounds is ignored "
                "under sync_mode='async' (the commit plane refuses "
                "the scan dispatch; capturing the commit program only)")
        if tel.enabled and tel.is_writer:
            from fedtorch_tpu.telemetry.costs import ProgramCostCapture
            cost_capture = ProgramCostCapture(
                ckpt_dir, compute_dtype=cfg.mesh.compute_dtype,
                arch=cfg.model.arch, batch_size=cfg.data.batch_size,
                local_steps=trainer.local_steps,
                k_online=trainer.k_online,
                num_devices=int(trainer.mesh.devices.size),
                backend=jax.default_backend(),
                device_kind=jax.devices()[0].device_kind,
                run_meta={"algorithm": cfg.effective_algorithm,
                          "sync_mode": cfg.federated.sync_mode,
                          "data_plane": cfg.data.data_plane},
                log=logger.log)
        # federation-plane observability (docs/observability.md
        # "Federation plane"): the per-client ledger accumulates the
        # cohort vectors the batched fetch now carries (cohort_stats
        # on, writer process only), and the observe-only anomaly
        # detector watches the finished metrics rows. Both host-only.
        ledger = None
        anomaly = None
        if tel.enabled and tel.is_writer and cfg.telemetry.cohort_stats:
            from fedtorch_tpu.telemetry.ledger import ClientLedger
            ledger = ClientLedger(
                ckpt_dir, num_clients=cfg.federated.num_clients,
                sketch_budget=cfg.telemetry.ledger_sketch_budget,
                seed=cfg.train.manual_seed,
                run_meta={"algorithm": cfg.effective_algorithm,
                          "robust_agg": cfg.fault.robust_agg,
                          "sync_mode": cfg.federated.sync_mode},
                log=logger.log)
            if ledger.load_existing():
                # elastic restarts ADOPT the run dir's ledger (the
                # program_costs.json convention) — counters resume
                # instead of overwriting the history with zeros
                logger.log("client ledger: adopted existing "
                           f"client_ledger.json ({ledger.rounds} "
                           "rounds)")
        if tel.enabled and cfg.telemetry.anomaly_zscore > 0.0:
            from fedtorch_tpu.telemetry.anomaly import (
                EwmaAnomalyDetector,
            )
            anomaly = EwmaAnomalyDetector(
                zscore=cfg.telemetry.anomaly_zscore)
        # privacy plane (robustness/privacy.py): the host-side RDP
        # accountant streams epsilon spend per committed round. EVERY
        # process accounts (the charge is deterministic, so budget
        # decisions stay SPMD-consistent without a collective); only
        # the writer persists. Participation probability is the run's
        # real cohort width over the population — the commit buffer m
        # on the async plane, k_online on the sync planes ('sparse'
        # k/C directly; 'perm' prefix selection charges equivalently).
        accountant = None
        dp_q = 0.0
        if cfg.fault.dp_armed:
            from fedtorch_tpu.robustness.privacy import (
                ACCOUNTANT_FILE, PrivacyAccountant,
            )
            accountant = PrivacyAccountant(
                cfg.fault.dp_noise_multiplier, cfg.fault.dp_delta)
            width = getattr(trainer, "buffer_size", None) \
                or trainer.k_online
            dp_q = min(1.0, width / float(cfg.federated.num_clients))
            if accountant.load_existing(ckpt_dir):
                # elastic restarts ADOPT the run dir's accountant (the
                # program_costs.json convention) — spend resumes, and
                # per-round-index dedup below makes re-run rounds
                # charge exactly once
                logger.log(
                    "privacy accountant: adopted existing "
                    f"{ACCOUNTANT_FILE} (eps_spent="
                    f"{accountant.epsilon():.4f} over "
                    f"{accountant.charged_rounds} rounds)")
        # still inside the guard: this fetch can raise too (device
        # fault, poisoned resume state) and must not leak the active
        # telemetry / a 'starting' intent for a dead run
        start_round = int(jax.device_get(server.round))
        tel.event("run.start", start_round=start_round, resumed=resumed,
                  num_comms=cfg.federated.num_comms)
    except BaseException:
        tel.health_update("error")
        _uninstall_host_plane()
        tel.close()
        raise
    results = {}
    loop_raised = False
    byz_attack_seen = False
    host_retries_seen = 0
    # consecutive sub-quorum rounds (availability lifecycle): a
    # persistent streak flips the health intent to 'degraded' below
    quorum_streak = 0
    # privacy budget lifecycle: True once 'degrade' flipped the run
    # noise-free — drives the 'degraded' health intent at exit
    dp_degraded = False
    # round-wall critical path (telemetry/critical_path.py): per-round
    # overlap efficiency from the DELTAS of the producer's cumulative
    # gather/H2D/wait gauges — pure host float math over values the
    # row already carries, zero extra device syncs
    from fedtorch_tpu.telemetry.critical_path import (
        StreamOverlapTracker,
    )
    overlap_tracker = StreamOverlapTracker()
    try:
        for r in range(start_round, cfg.federated.num_comms):
            # privacy budget lifecycle (docs/robustness.md "Privacy
            # plane"): pre-check affordability BEFORE dispatching
            # round r — 'stop' ends the run at the LAST affordable
            # round (never one past the budget), 'degrade' flips the
            # traced noise scale to 0.0 (data, not program: no
            # retrace) and keeps going noise-free. Deterministic on
            # every process, so the SPMD decision needs no collective.
            if accountant is not None and not dp_degraded \
                    and cfg.fault.dp_epsilon_budget > 0.0 \
                    and accountant.preview_epsilon(dp_q) \
                    > cfg.fault.dp_epsilon_budget:
                action = cfg.fault.dp_budget_action
                spent = accountant.epsilon()
                tel.event("privacy.budget_exhausted", round=r,
                          action=action, epsilon_spent=spent,
                          epsilon_budget=cfg.fault.dp_epsilon_budget,
                          delta=cfg.fault.dp_delta,
                          charged_rounds=accountant.charged_rounds)
                logger.log(
                    f"privacy budget exhausted before round {r}: "
                    f"eps_spent={spent:.4f} of "
                    f"{cfg.fault.dp_epsilon_budget} (action="
                    f"{action})")
                results["dp_exhausted"] = True
                results["dp_exhausted_at_round"] = r
                if action == "stop":
                    break
                server = trainer.dp_set_noise_scale(server, 0.0)
                dp_degraded = True
            timer.new_round()
            # copy, not alias: the round jit donates the server buffers
            prev_params = jax.tree.map(jnp.copy, server.params) \
                if cfg.checkpoint.track_model_aggregation else None
            timer.start("round")
            # the "round" span covers dispatch through completion of
            # the jitted round/commit program — what the 90%-non-MXU
            # attribution question is asked against
            with tel.span("round", round=r):
                with tel.span("round.dispatch", round=r):
                    server, clients, metrics = run_round(server, clients)
                if supervisor is None:
                    # the supervisor's health check already blocked
                    with tel.span("round.wait", round=r):
                        jax.block_until_ready(server.params)
            round_time = timer.stop("round")
            # ONE batched device->host fetch for everything this loop
            # logs (round_host_fetch: one compiled program, one array)
            # — per-scalar float() here would serialize a transfer per
            # metric per round (lint FTL001). The ledger's per-client
            # cohort vectors ride the SAME device_get when cohort_stats
            # is on. A supervised healthy round already fetched the
            # scalar dict for its health check: reuse it (only the [k]
            # cohort vectors transfer).
            led_dev = trainer.cohort_fetch_dev(metrics) \
                if ledger is not None else None
            led = None
            fetch_t0 = time.perf_counter()
            if supervisor is not None and \
                    supervisor.last_scalars is not None:
                sc = supervisor.last_scalars
                if led_dev is not None:
                    led = jax.device_get(led_dev)
            else:
                with tel.span("scalar_fetch", round=r):
                    sc, led = trainer.round_host_fetch(
                        clients, metrics, led_dev)
            fetch_s = time.perf_counter() - fetch_t0
            timer.add_comm(num_bytes=sc["comm_bytes"])
            # the scalar fetch blocked on the round's results: the
            # round genuinely completed — feed the stall watchdog
            watchdog.heartbeat(r)
            if accountant is not None and not dp_degraded:
                # charge the COMMITTED round (after degrade the noise
                # is off, so spend freezes); charge_round dedups by
                # round index — supervisor retries and resume re-runs
                # charge exactly once
                accountant.charge_round(r, dp_q)

            if cost_capture is not None and not cost_capture.captured \
                    and not cost_capture.load_existing():
                # once, at the first completed round (elastic restarts
                # adopt the run dir's existing capture instead of
                # lowering the twins again); a failure turns the
                # device gauges off, never the run
                with tel.span("cost_capture"):
                    try:
                        programs, primary = \
                            trainer.lowered_cost_programs(
                                server, clients,
                                num_scan_rounds=cfg.telemetry
                                .cost_capture_scan_rounds)
                        try:
                            from fedtorch_tpu.parallel.evaluate import (
                                lowered_eval_program,
                            )
                            programs["eval"] = lowered_eval_program(
                                model, server.params, fed_data.test_x,
                                fed_data.test_y)
                        except Exception as e:
                            logger.log("cost capture: eval program "
                                       f"skipped ({e})")
                        cost_capture.capture(programs, primary=primary)
                    except Exception as e:
                        cost_capture.captured = True
                        logger.log(f"cost capture: lowering failed "
                                   f"({e}); device gauges off")

            with tel.span("round.record", round=r):
                if cfg.fault.chaos_enabled or cfg.fault.guard_updates:
                    if sc["dropped"] or sc["rejected"] or sc["clipped"] \
                            or sc["stragglers"] or sc["byzantine"]:
                        logger.log(
                            f"Round {r}: faults — "
                            f"dropped={sc['dropped']:.0f} "
                            f"stragglers={sc['stragglers']:.0f} "
                            f"rejected={sc['rejected']:.0f} "
                            f"clipped={sc['clipped']:.0f} "
                            f"byzantine={sc['byzantine']:.0f}")
                    if sc["byzantine"] and not byz_attack_seen:
                        # one attack event per run, at the first observed
                        # injection — monitors key on this, not on scanning
                        # every row's counter
                        byz_attack_seen = True
                        tel.event("chaos.byzantine_attack", round=r,
                                  mode=cfg.fault.byzantine_mode,
                                  rate=cfg.fault.byzantine_rate,
                                  scale=cfg.fault.byzantine_scale,
                                  robust_agg=cfg.fault.robust_agg)
                    if supervisor is None and _all_rejected(sc):
                        # renorm scale hit 0: every surviving update was
                        # rejected (or every client crashed) — the server
                        # held this round. With a supervisor the same
                        # detection runs inside its health path.
                        logger.log(f"Round {r}: guards rejected EVERY "
                                   "update — server held (renorm scale 0)")
                        tel.event("guards.all_rejected", round=r,
                                  n_online=sc["n_online"],
                                  rejected=sc["rejected"],
                                  dropped=sc["dropped"])

                if cfg.checkpoint.check_model_at_sync:
                    norms = jax.device_get(model_norms(server.params))
                    logger.log(f"Round {r}: server model l2="
                               f"{float(norms['l2']):.4f} "
                               f"max|w|={float(norms['max_abs']):.4f}")
                if prev_params is not None:
                    tr = jax.device_get(
                        aggregation_tracking(prev_params, server.params))
                    logger.log(f"Round {r}: aggregation cosine="
                               f"{float(tr['cosine']):.6f} "
                               f"distance={float(tr['distance']):.6f}")

                n_online = max(sc["n_online"], 1.0)
                epoch = sc["mean_epoch"]
                logger.log_train(r, epoch, sc["loss_sum"] / n_online,
                                 sc["acc_sum"] / n_online, sc["lr"],
                                 comm_bytes=sc["comm_bytes"],
                                 round_time=round_time)

            eval_s = checkpoint_s = None
            if (r + 1) % cfg.train.eval_freq == 0:
                timer.start("eval")
                with tel.span("eval", round=r).rss():
                    # one transfer for the whole EvalResult pytree
                    res = evaluate_to_host(
                        model, server.params, fed_data.test_x,
                        fed_data.test_y)
                eval_s = timer.stop("eval")
                top1 = float(res.top1)
                is_best = top1 > best_prec1
                best_prec1 = max(best_prec1, top1)
                logger.log_val(r, "test", float(res.loss), top1,
                               float(res.top5), best=best_prec1)
                if cfg.train.per_class_acc:
                    from fedtorch_tpu.models.common import num_classes_of
                    from fedtorch_tpu.parallel import evaluate_per_class
                    accs, counts = evaluate_per_class(
                        model, server.params, fed_data.test_x,
                        fed_data.test_y, num_classes_of(cfg.data.dataset))
                    logger.log("Round: {}. Per-class acc: {}".format(
                        r, [round(float(a), 4) for a in accs]))
                if accountant is not None and tel.is_writer:
                    # persist spend BEFORE the checkpoint that could
                    # become a resume point: any adopted restart then
                    # sees spend >= its round (never-forget-spend half
                    # of the resume contract)
                    accountant.save(ckpt_dir)
                timer.start("checkpoint")
                with tel.span("checkpoint", round=r).rss():
                    saver(ckpt_dir, server, clients, cfg, best_prec1,
                          is_best,
                          save_all=cfg.checkpoint.save_all_models,
                          save_some_rounds=save_rounds)
                last_saved_round = r
                # lost-write watermark at enqueue time: the drain's
                # skip branch compares against it to detect THIS
                # round's async write failing behind our back
                lost_at_save = async_ckpt.lost_writes \
                    if async_ckpt is not None else 0
                checkpoint_s = timer.stop("checkpoint")
                if cfg.federated.personal and fed_data.val is not None \
                        and cfg.effective_algorithm in (
                            "apfl", "perfedme", "perfedavg"):
                    _, _, summary = evaluate_personal(
                        model, clients.aux, clients.params,
                        trainer.val_data, cfg.effective_algorithm)
                    logger.log_val(r, "validation_personal",
                                   summary["loss_mean"],
                                   summary["acc_mean"])
                results["test_top1"] = top1

            with tel.span("round.record", round=r):
                # one schema-versioned metrics row per round (async: per
                # commit), populated from the already-fetched scalar dict
                # plus host-only subsystem gauges — zero extra transfers
                n_onl = max(sc["n_online"], 1.0)
                row = {
                    "round": r, "round_s": round_time,
                    "loss": sc["loss_sum"] / n_onl,
                    "acc": sc["acc_sum"] / n_onl, "lr": sc["lr"],
                    "n_online": sc["n_online"],
                    "comm_bytes": sc["comm_bytes"],
                    "mean_epoch": sc["mean_epoch"], "fetch_s": fetch_s,
                    "dropped": sc["dropped"],
                    "stragglers": sc["stragglers"],
                    "rejected": sc["rejected"], "clipped": sc["clipped"],
                    "staleness": sc["staleness"],
                    "byzantine": sc["byzantine"],
                    "robust_selected": sc["robust_selected"],
                    "robust_trimmed": sc["robust_trimmed"],
                    # deployment-realism lifecycle counters — same fetch
                    "avail_dropped": sc["avail_dropped"],
                    "deadline_missed": sc["deadline_missed"],
                    "quorum_degraded": sc["quorum_degraded"],
                }
                if eval_s is not None:
                    row["eval_s"] = eval_s
                    # already host floats (the eval device_get above) —
                    # riding the row costs nothing extra
                    row["test_top1"] = top1
                    row["best_top1"] = best_prec1
                if checkpoint_s is not None:
                    row["checkpoint_s"] = checkpoint_s
                if "cohort_dispersion" in sc:
                    # the heterogeneity gauge (cohort_stats on) — already
                    # part of the batched scalar fetch
                    row["cohort_dispersion"] = sc["cohort_dispersion"]
                if "dp_clipped_frac" in sc:
                    # privacy-plane gauges (DP armed) — same batched fetch
                    row["dp_clipped_frac"] = sc["dp_clipped_frac"]
                    row["dp_noise_sigma"] = sc["dp_noise_sigma"]
                # the model's own gauges (models/common.py
                # ``is_token_model``) — same batched fetch
                row.update((name, sc[name])
                           for name in trainer.gauge_names)
                if accountant is not None:
                    # host-side accountant read: pure f64 math, no sync
                    row["dp_epsilon_spent"] = accountant.epsilon()
                if led is not None:
                    # cohort norm quantiles + the per-client ledger fold
                    # (host numpy from the same fetch; O(k) update)
                    nq = led["norm_q"]
                    row.update({
                        "cohort_norm_min": float(nq[0]),
                        "cohort_norm_q25": float(nq[1]),
                        "cohort_norm_med": float(nq[2]),
                        "cohort_norm_q75": float(nq[3]),
                        "cohort_norm_max": float(nq[4]),
                    })
                    ledger.update(r, led)
                    row.update(ledger.stats())
                row.update(trainer.telemetry_gauges())
                overlap_eff = overlap_tracker.observe(row)
                if overlap_eff is not None:
                    # stream plane: the fraction of this round's producer
                    # gather+H2D wall hidden under device compute
                    row["overlap_efficiency"] = overlap_eff
                if cost_capture is not None:
                    # measured MFU + HBM watermark pair — empty until the
                    # capture above succeeded, host-side either way
                    row.update(cost_capture.round_gauges(round_time))
                if async_ckpt is not None:
                    row.update(async_ckpt.stats())
                if supervisor is not None:
                    row.update(sup_rollbacks=float(supervisor.stats.rollbacks),
                               sup_retries=float(supervisor.stats.retries),
                               sup_skipped=float(
                                   supervisor.stats.skipped_rounds),
                               # skip-cause split (fault vs sub-quorum
                               # abort) — docs/robustness.md "Deployment
                               # realism"
                               sup_skipped_fault=float(
                                   supervisor.stats.skipped_fault),
                               sup_skipped_quorum=float(
                                   supervisor.stats.skipped_quorum))
                # host-plane recovery gauges: retries/recoveries/degraded
                # seams (and injected-fault count when a drill is armed) —
                # host counters, zero extra device syncs
                row.update(recovery.stats())
                if injector is not None:
                    row.update(injector.stats())
                tel.round_row(row)
                if sc["quorum_degraded"] > 0:
                    # a sub-quorum round that committed its renormalized
                    # partial cohort (degrade action) or is about to be
                    # escalated (abort retries exhausted into a skip) —
                    # the per-round operator signal behind the 'degraded'
                    # health intent below
                    tel.event("lifecycle.quorum_degraded", round=r,
                              n_online=sc["n_online"],
                              avail_dropped=sc["avail_dropped"],
                              deadline_missed=sc["deadline_missed"])
                if anomaly is not None:
                    # observe-only EWMA z-score pass over the finished row
                    # (telemetry/anomaly.py): events + report fodder, no
                    # control flow
                    for a in anomaly.observe(row):
                        tel.event("anomaly.detected", round=r, **a)
                if cfg.telemetry.level == "debug" and (r + 1) % 25 == 0:
                    # debug cadence snapshot of the async staleness
                    # histogram: a hard-killed run (watchdog os._exit)
                    # keeps at most 25 commits of histogram, not all of it
                    hist = trainer.staleness_histogram()
                    if hist:
                        tel.event("async.staleness_hist", round=r,
                                  snapshot="debug",
                                  hist={str(k): v
                                        for k, v in sorted(hist.items())})
                # health: r+1 rounds complete — same convention as
                # checkpoint.json's "round", so monitors can compare the
                # live counter against the last durable one. Intent
                # reflects the host-plane recovery state: 'degraded' while
                # any seam runs in degraded mode, 'recovering' on a round
                # that absorbed a host-seam retry, 'running' otherwise —
                # the run IS progressing in all three.
                host_retries_now = recovery.total_retries()
                quorum_streak = quorum_streak + 1 \
                    if sc["quorum_degraded"] > 0 else 0
                if recovery.degraded or quorum_streak >= 3 or dp_degraded:
                    # host seam running degraded, OR the availability
                    # lifecycle committing sub-quorum cohorts for 3+
                    # consecutive rounds, OR the privacy budget exhausted
                    # into noise-free continuation — progressing, but an
                    # operator should look (docs/robustness.md)
                    intent = "degraded"
                elif host_retries_now > host_retries_seen:
                    intent = "recovering"
                else:
                    intent = "running"
                host_retries_seen = host_retries_now
                tel.health_update(intent, round_idx=r + 1,
                                  staleness=sc["staleness"])

            if round_callback is not None:
                round_callback(r, trainer, server, clients, metrics)
            if sc.get("stop"):
                # SPMD-agreed stop (every process computed the same
                # cross-host max): drain at the round boundary — write
                # a final checkpoint and leave with the restartable
                # exit code instead of dying mid-state. The watchdog
                # must disarm FIRST: a slow final write would read as
                # a stall and os._exit would lose the drain.
                watchdog.stop()
                logger.log(f"preemption: stop requested "
                           f"({preempt.reason or 'peer host'}); "
                           f"draining after round {r}")
                tel.event("preempt.drain", round=r,
                          reason=preempt.reason or "peer host")
                hist = trainer.staleness_histogram()
                if hist:
                    # drain-path snapshot (async plane): the final
                    # emission reads the histogram after the stream
                    # teardown; snapshotting here makes the preempted
                    # run's histogram durable even if the drain's own
                    # checkpoint write later raises
                    tel.event("async.staleness_hist", round=r,
                              snapshot="drain",
                              hist={str(k): v
                                    for k, v in sorted(hist.items())})
                tel.health_update("drain", round_idx=r + 1)
                # the resume point the restart depends on must be
                # DURABLE before exit 75 — a failure here must RAISE,
                # not be recorded as a lost background write. When
                # this round's eval branch already saved, drain the
                # async queue and only redo the (collective-snapshot)
                # write if that queued write was lost.
                final_ckpt_needed = last_saved_round != r
                if not final_ckpt_needed and async_ckpt is not None:
                    async_ckpt.wait()
                    final_ckpt_needed = \
                        async_ckpt.lost_writes > lost_at_save
                    if final_ckpt_needed:
                        logger.log("preemption: this round's async "
                                   "checkpoint was lost — rewriting "
                                   "synchronously before exit")
                if final_ckpt_needed:
                    timer.start("checkpoint")
                    with tel.span("checkpoint", round=r, drain=True):
                        if async_ckpt is not None:
                            # an older queued write landing AFTER the
                            # final sync write would roll the resume
                            # point backwards — drain the queue first
                            async_ckpt.wait()
                        save_checkpoint(
                            ckpt_dir, server, clients, cfg,
                            best_prec1, False,
                            save_all=cfg.checkpoint.save_all_models,
                            save_some_rounds=save_rounds)
                    timer.stop("checkpoint")
                results["preempted"] = True
                results["preempted_at_round"] = r
                break
    except BaseException:
        loop_raised = True
        raise
    finally:
        # the drain itself must not race the watchdog (a slow final
        # write would read as a stall), and the handlers must never
        # outlive the loop in library callers
        watchdog.stop()
        preempt.restore()
        # read the staleness histogram BEFORE the stream teardown: the
        # async trainer's invalidate_stream drops the event scheduler
        # that owns it, which silently lost the run-end
        # async.staleness_hist event on every CLI run (the trainer
        # also stashes it across invalidation now — belt and braces)
        final_hist = trainer.staleness_histogram()
        # streaming data plane: stop the feed producer and drop any
        # in-flight prefetch — a preemption drain (exit 75) must not
        # leave a worker thread blocked on the feed queue, and a
        # library caller resuming this trainer later re-syncs cleanly
        trainer.invalidate_stream()
        flush_raised = False
        try:
            if async_ckpt is not None:
                # flush pending writes even when the loop raised — the
                # checkpoint the user would resume from must hit disk.
                # A background write that failed past its retries was
                # already recorded (ckpt.degraded event + lost-write
                # counters; the drain path writes its final checkpoint
                # synchronously so ITS failure raises at the save) —
                # close() itself raising is a defensive residue, kept
                # because it must not MASK the loop's own exception
                # while still surfacing when the loop succeeded.
                timer.start("checkpoint")
                try:
                    async_ckpt.close()
                except Exception as e:
                    flush_raised = True
                    if loop_raised:
                        logger.log("WARNING: async checkpoint flush "
                                   "failed while handling another "
                                   f"error: {e}")
                    else:
                        raise
                finally:
                    timer.stop("checkpoint")
        finally:
            # final telemetry: the staleness histogram (async plane),
            # the ledger flush, the run-end event, the exit intent,
            # and the trace export — best-effort bookkeeping that must
            # never mask the loop's outcome (the emitters, the ledger
            # flush and Telemetry.close never raise)
            if final_hist:
                tel.event("async.staleness_hist", snapshot="final",
                          hist={str(k): v
                                for k, v in sorted(final_hist.items())})
            if ledger is not None:
                ledger.flush()
            if accountant is not None and tel.is_writer:
                # final durable spend (save absorbs I/O failure — the
                # bookkeeping never masks the loop's outcome)
                accountant.save(ckpt_dir)
            if anomaly is not None:
                tel.event("anomaly.summary", fields=anomaly.summary())
            tel.event("run.end",
                      preempted=bool(results.get("preempted")),
                      raised=loop_raised or flush_raised)
            if loop_raised or flush_raised:
                tel.health_update("error")
            elif results.get("preempted"):
                tel.health_update("preempted")
            elif quorum_streak >= 3 or dp_degraded:
                # the run finished, but its tail was a persistent
                # sub-quorum streak OR a noise-free privacy 'degrade'
                # continuation — keep the operator signal instead of
                # overwriting it with a clean 'complete'. (A budget
                # 'stop' lands in the else: ending at the last
                # affordable round IS the clean outcome.)
                tel.health_update("degraded")
            else:
                tel.health_update("complete")
            _uninstall_host_plane()
            tel.close()
    results["best_top1"] = best_prec1
    if accountant is not None:
        results["dp"] = {
            "epsilon_spent": accountant.epsilon(),
            "delta": cfg.fault.dp_delta,
            "charged_rounds": accountant.charged_rounds,
            "exhausted": bool(results.get("dp_exhausted")),
            "degraded": dp_degraded,
        }
    if supervisor is not None:
        st = supervisor.stats
        results["supervisor"] = {
            "rounds": st.rounds, "retries": st.retries,
            "rollbacks": st.rollbacks,
            "skipped_rounds": st.skipped_rounds,
            "skipped_fault": st.skipped_fault,
            "skipped_quorum": st.skipped_quorum,
            "disk_restores": st.disk_restores,
            "all_rejected_rounds": st.all_rejected_rounds,
            "last_good_round": st.last_good_round}
        if st.rollbacks:
            logger.log(f"supervisor: {st.rollbacks} rollback(s), "
                       f"{st.retries} retrie(s), {st.skipped_rounds} "
                       "skipped round(s)")
    rec_stats = recovery.stats()
    if injector is not None:
        rec_stats.update(injector.stats())
        rec_stats["host_fault_fires"] = injector.fire_counts()
    if any(bool(v) for v in rec_stats.values()):
        results["host_recovery"] = rec_stats
        logger.log(f"host plane: {rec_stats}")
    results["timer"] = timer.summary()
    logger.log(f"phase timers: {timer.summary()}")
    if results.get("preempted"):
        from fedtorch_tpu.robustness import RESTART_EXIT_CODE
        logger.log("preemption: final checkpoint drained and flushed; "
                   f"restartable exit (code {RESTART_EXIT_CODE}) — "
                   "run_elastic/supervise will relaunch with --resume")
    return results


def main(argv=None):
    if argv is None:
        import sys
        argv = sys.argv[1:]
    if argv and argv[0] == "lint":
        # `fedtorch-tpu lint [...]` — the static tracing-hazard
        # analyzer (docs/static_analysis.md); stdlib-only, never
        # initializes jax
        from fedtorch_tpu.lint.cli import main as lint_main
        return lint_main(argv[1:])
    if argv and argv[0] == "audit":
        # `fedtorch-tpu audit [...]` — the program-level + registry-
        # drift audit (docs/static_analysis.md "The program audit"):
        # abstractly lowers every legal round-program builder cell and
        # checks the HLO/jaxpr (FTP rules), then cross-checks the five
        # hand-maintained registries (FTC rules). Initializes jax
        # (CPU is fine); --registry-only stays stdlib.
        from fedtorch_tpu.lint.cli import main as lint_main
        return lint_main(["--audit"] + argv[1:])
    if argv and argv[0] == "report":
        # `fedtorch-tpu report <run_dir>` — summarize a run dir's
        # telemetry (docs/observability.md); stdlib-only, never
        # initializes jax
        from fedtorch_tpu.tools.report import main as report_main
        return report_main(argv[1:])
    if argv and argv[0] == "watch":
        # `fedtorch-tpu watch <run_dir>` — live console over a
        # running run's health/metrics/events (docs/observability.md
        # "Operating and comparing runs"); stdlib-only, never
        # initializes jax; one-shot snapshot on non-tty
        from fedtorch_tpu.tools.watch import main as watch_main
        return watch_main(argv[1:])
    if argv and argv[0] == "compare":
        # `fedtorch-tpu compare A B [--gate gates.json]` — noise-aware
        # run-dir diff with regression gating (exit 1 on a gated
        # regression); stdlib-only, never initializes jax
        from fedtorch_tpu.tools.compare import main as compare_main
        return compare_main(argv[1:])
    if argv and argv[0] == "runs":
        # `fedtorch-tpu runs <root>` — index run dirs into
        # runs_index.json and list/filter them; stdlib-only, never
        # initializes jax
        from fedtorch_tpu.telemetry.runs import main as runs_main
        return runs_main(argv[1:])
    if argv and argv[0] == "supervise":
        # `fedtorch-tpu supervise [opts] -- <training command>` — the
        # per-host auto-restart harness (robustness/harness.py):
        # relaunches the command with --resume on restartable exits
        from fedtorch_tpu.robustness.harness import main as harness_main
        return harness_main(argv[1:])
    args = build_parser().parse_args(argv)
    cfg = args_to_config(args)
    results = run_experiment(cfg, download=args.download)
    if isinstance(results, dict) and results.get("preempted"):
        # EX_TEMPFAIL: the restart-harness contract — raised (not
        # returned) so `python -m fedtorch_tpu.cli` and the console
        # script both exit 75
        from fedtorch_tpu.robustness import RESTART_EXIT_CODE
        raise SystemExit(RESTART_EXIT_CODE)
    return results


if __name__ == "__main__":
    _result = main()
    if isinstance(_result, int):  # lint / supervise exit codes
        raise SystemExit(_result)
