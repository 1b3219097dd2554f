#!/bin/bash
# CANONICAL parameterized TPU capture entry point.
#
#     bash scripts/tpu_capture.sh [step ...]
#
# Runs the named steps sequentially, one process at a time: a chip
# belongs to one process, and this shell never touches JAX. With no
# arguments, runs the full default list. Every step that measures
# exits non-zero without a TPU. Through the chip tool, pass the whole
# invocation as ONE command so the steps share the compile cache.
# Steps:
#
#   bench            bench.py                     (one JSON line)
#   bench-unroll     BENCH_SCAN_UNROLL=4 bench.py (unroll A/B)
#   bench-dispatch   BENCH_SINGLE_DISPATCH=0      (dispatch A/B)
#   bench-streaming  BENCH_STREAMING=1 bench.py   (streaming-plane A/B
#                        side: host store + round-ahead prefetch)
#   stream           scripts/stream_bench.py      -> STREAM_AB.json
#                        (device vs stream wall-time + bytes moved +
#                         residency + retrace count on the real chip)
#   population       STREAM_BENCH_POPULATION=1 scripts/stream_bench.py
#                        -> MILLION_CLIENT_AB.json (million-client
#                         drill: C in {10^3,10^5,10^6} on the mmap
#                         store + sparse sampling — round wall flat in
#                         C, residency mapped-not-resident, bitwise
#                         mmap-vs-RAM parity, 0 retraces) + the
#                         artifacts/population_ab/{a,b} run dirs,
#                         gated by compare --gate
#                         tests/data/ops_runs/population_gates.json
#                         -> MILLION_CLIENT_COMPARE.json
#                         (docs/performance.md "The million-client
#                         store")
#   podscale         scripts/podscale_bench.py  -> PODSCALE_AB.json
#                        (shard sweep: rounds/sec + clients/sec vs
#                         mesh.client_shards, bitwise parity vs the
#                         1-shard twin, 0 retraces) + the
#                         artifacts/podscale_northstar run dir, gated
#                         against the previous window's rotated copy
#                         by tests/data/ops_runs/podscale_gates.json
#                         -> PODSCALE_COMPARE.json; regressed
#                         clients/sec exits nonzero
#                         (docs/performance.md "Pod-scale round
#                         programs")
#   async            scripts/async_bench.py       -> ASYNC_AB.json
#                        (sync round clock vs FedBuff-style commit
#                         clock under the straggler-heavy schedule +
#                         on-chip ms/commit + accuracy parity)
#   attack           scripts/chaos_suite.py --attack-matrix
#                        -> ATTACK_AB.json (byzantine attack x robust
#                         aggregator grid on the IID pool: 25%
#                         sign_flip must break plain mean by >5 pts
#                         while >=1 robust rule holds within 5 —
#                         docs/robustness.md threat-model table)
#   avail            scripts/chaos_suite.py --availability-matrix
#                        -> AVAIL_AB.json (deployment-realism drill:
#                         default-model arrivals bitwise vs the raw
#                         legacy straggler chain, armed trace-model
#                         lifecycle seeded-replayable + trace-once,
#                         sub-quorum degrade completes where abort
#                         escalates into the supervisor, async
#                         trace-model dropouts deterministic —
#                         docs/robustness.md "Deployment realism")
#   privacy          scripts/chaos_suite.py --privacy-matrix
#                        -> DP_AB.json (DP-FedAvg drill: DP-off leg
#                         HLO-byte-identical + bitwise replay, RDP
#                         accountant within 1% of the closed form,
#                         epsilon-vs-accuracy frontier at 3 budgets
#                         trace-once, DP x trimmed_mean x byzantine
#                         layering, both budget-exhaustion actions —
#                         docs/robustness.md "Privacy plane")
#   builder-matrix   scripts/chaos_suite.py --builder-matrix
#                        -> BUILDER_MATRIX.json (round-program-builder
#                         smoke: scanned device, scanned streamed and
#                         feed-commit cells under chaos + guards, each
#                         trace-once and bitwise vs its reference —
#                         docs/performance.md "Round-program builder")
#   host-chaos       scripts/chaos_suite.py --host-fault-matrix
#                        -> HOST_CHAOS_AB.json (host-plane fault
#                         drill: every HOST_FAULT_SEAMS seam injected
#                         at the default rate must complete with a
#                         bitwise-identical trajectory, fire its
#                         retry/degraded counters+events, and a dead
#                         stream producer must rebuild instead of
#                         aborting — docs/robustness.md "Host plane")
#   cohort           scripts/chaos_suite.py --ledger-attack
#                        -> COHORT_AB.json (ledger-separation drill:
#                         a real CLI run per robust rule with the
#                         byzantine cohort + --cohort_stats armed; the
#                         persisted client_ledger.json suspicion
#                         ranking must separate the adversarial cohort
#                         — precision/recall per rule;
#                         docs/observability.md "Federation plane")
#   telemetry        scripts/telemetry_bench.py   -> TELEMETRY_AB.json
#                        (off/default/debug overhead A/B on the
#                         north-star config, <=1% acceptance) +
#                         artifacts/telemetry_northstar/ metrics.jsonl
#                         + Perfetto trace.json capture
#   compare          fedtorch-tpu compare of the fresh
#                        artifacts/telemetry_northstar capture against
#                        the previous armed capture's rotated copy
#                        (artifacts/telemetry_northstar_prev), gated
#                        by tests/data/ops_runs/gates.json
#                        -> TELEMETRY_COMPARE.json; nonzero exit on a
#                         gated regression (docs/observability.md
#                         "Operating and comparing runs"). Always
#                         rotates the fresh capture into _prev for the
#                         next window; first window is baseline-only.
#   conv-ab          BENCH_CONV_IMPL=matmul|conv  (lowering A/B, both)
#   zoo              scripts/tpu_zoo_check.py     -> TPU_ZOO.json
#   pallas           scripts/pallas_tpu_check.py  (kernel correctness)
#   flash-train      scripts/flash_train_bench.py -> FLASH_TRAIN.json
#   flash-sweep      scripts/flash_block_sweep.py -> FLASH_BLOCK_SWEEP.json
#   vmap             scripts/vmap_penalty_bench.py -> VMAP_PENALTY.json
#   mfu              MFU_PROFILE=1 scripts/mfu_sweep.py
#                        -> MFU_SWEEP.json (now incl. the client-fused
#                           configs) + artifacts/trace_northstar{,_fused}
#                           on-chip profiler traces, piped through
#                           tools/trace_attrib into
#                           artifacts/attrib_northstar{,_fused}.json/.txt
#                           (the device-time category table —
#                           docs/observability.md "Device-side")
#   moe              scripts/moe_ab_bench.py      -> MOE_AB.json
#   seqpar           scripts/seqpar_tpu_probe.py  -> SEQPAR_TPU_PROBE.json
#   baseline         scripts/baseline_suite.py    -> BASELINE_SUITE.json
#   curves           scripts/northstar_synthetic.py -> NORTHSTAR_CURVE_*.json
#   audit            python -m fedtorch_tpu.lint --audit
#                        -> PROGRAM_AUDIT.json (program-level FTP +
#                         registry FTC audit ON THE TPU BACKEND: every
#                         legal builder cell abstractly lowered and
#                         checked for f64/f32-in-bf16 promotion, host
#                         transfers, donation aliasing, collective
#                         budget, baked constants, peak-HBM watermark
#                         — the tier-1 CPU audit re-run against the
#                         real Mosaic/TPU lowering;
#                         docs/static_analysis.md "The program audit")
#   concurrency      python -m fedtorch_tpu.lint --concurrency
#                        -> CONCURRENCY_AUDIT.json (host-plane FTH
#                         lock/thread audit: lock-order cycles,
#                         emit-under-lock, unlocked thread-shared
#                         state, unbounded blocking, thread hygiene,
#                         non-atomic run-dir writes — stdlib-only;
#                         docs/static_analysis.md "The concurrency
#                         audit")
set -u
cd "$(dirname "$0")/.." || exit 1

# Callers set FAILED=0 before the first call.
run() {
    echo "=== $* ==="
    "$@"
    local rc=$?
    echo "=== rc=$rc ==="
    if [ $rc -ne 0 ]; then FAILED=1; fi
    return $rc
}

# audit rides early: it is seconds of abstract lowering and proves the
# program invariants on the real backend before the long benches run
DEFAULT_STEPS="audit concurrency mfu stream population podscale \
builder-matrix avail \
privacy async attack host-chaos cohort telemetry compare bench-streaming \
bench-dispatch bench-unroll bench zoo pallas flash-train vmap baseline"
STEPS="${*:-$DEFAULT_STEPS}"

echo "[tpu_capture] capturing: $STEPS"
FAILED=0
for step in $STEPS; do
    case "$step" in
        bench)          run python bench.py ;;
        bench-unroll)   run env BENCH_SCAN_UNROLL=4 python bench.py ;;
        bench-dispatch) run env BENCH_SINGLE_DISPATCH=0 python bench.py ;;
        bench-streaming) run env BENCH_STREAMING=1 python bench.py ;;
        stream)         run python scripts/stream_bench.py ;;
        population)     run env STREAM_BENCH_POPULATION=1 \
                            python scripts/stream_bench.py
                        run python -m fedtorch_tpu.tools.compare \
                            artifacts/population_ab/a \
                            artifacts/population_ab/b \
                            --gate tests/data/ops_runs/population_gates.json \
                            --out MILLION_CLIENT_COMPARE.json ;;
        podscale)       # pod-scale shard sweep (ISSUE 20): rounds/sec
                        # + clients/sec vs client_shards, then gate the
                        # fresh largest-shard window against the
                        # previous one (same freshness-guard + rotate
                        # idiom as the telemetry compare step: a run
                        # dir not newer than _prev means the bench
                        # failed this window — skip the scaling gate
                        # rather than diff stale data against itself)
                        run python scripts/podscale_bench.py
                        if [ -d artifacts/podscale_northstar_prev ] \
                            && [ ! artifacts/podscale_northstar/metrics.jsonl \
                                 -nt artifacts/podscale_northstar_prev/metrics.jsonl ]; then
                            echo "[tpu_capture] podscale: capture is not" \
                                "newer than _prev (bench skipped/failed" \
                                "this window?) — skipping scaling gate"
                            FAILED=1
                        else
                            if [ -d artifacts/podscale_northstar_prev ]; then
                                run python -m fedtorch_tpu.tools.compare \
                                    artifacts/podscale_northstar_prev \
                                    artifacts/podscale_northstar \
                                    --gate tests/data/ops_runs/podscale_gates.json \
                                    --out PODSCALE_COMPARE.json
                            else
                                echo "[tpu_capture] podscale: no previous" \
                                    "capture — recording baseline only"
                            fi
                            if [ -d artifacts/podscale_northstar ]; then
                                rm -rf artifacts/podscale_northstar_prev
                                cp -r artifacts/podscale_northstar \
                                    artifacts/podscale_northstar_prev
                            fi
                        fi ;;
        async)          run python scripts/async_bench.py ;;
        attack)         run python scripts/chaos_suite.py \
                            --attack-matrix --rounds 25 \
                            --attack-out ATTACK_AB.json ;;
        builder-matrix) run python scripts/chaos_suite.py \
                            --builder-matrix --rounds 8 \
                            --builder-out BUILDER_MATRIX.json ;;
        avail)          run python scripts/chaos_suite.py \
                            --availability-matrix --rounds 12 \
                            --avail-out AVAIL_AB.json ;;
        privacy)        run python scripts/chaos_suite.py \
                            --privacy-matrix --rounds 12 \
                            --privacy-out DP_AB.json ;;
        host-chaos)     run python scripts/chaos_suite.py \
                            --host-fault-matrix --rounds 12 \
                            --host-out HOST_CHAOS_AB.json ;;
        cohort)         run python scripts/chaos_suite.py \
                            --ledger-attack --rounds 25 --seed 6 \
                            --ledger-out COHORT_AB.json ;;
        telemetry)      run python scripts/telemetry_bench.py \
                            --capture-run artifacts/telemetry_northstar ;;
        compare)        # regression-gate the fresh telemetry capture
                        # against the previous window's (rotated) one;
                        # stdlib-only. Freshness
                        # guard: _prev is rotated (cp -r, mtimes reset
                        # to rotation time) AFTER each capture, so a
                        # capture that is not newer than _prev means
                        # the telemetry step did NOT run this window —
                        # comparing would diff stale data against its
                        # own copy and report a bogus green. Skip the
                        # compare AND the rotation in that case.
                        if [ -d artifacts/telemetry_northstar_prev ] \
                            && [ ! artifacts/telemetry_northstar/metrics.jsonl \
                                 -nt artifacts/telemetry_northstar_prev/metrics.jsonl ]; then
                            echo "[tpu_capture] compare: capture is not" \
                                "newer than _prev (telemetry step" \
                                "skipped/failed this window?) — skipping"
                            FAILED=1
                        else
                            if [ -d artifacts/telemetry_northstar_prev ]; then
                                run python -m fedtorch_tpu.tools.compare \
                                    artifacts/telemetry_northstar_prev \
                                    artifacts/telemetry_northstar \
                                    --gate tests/data/ops_runs/gates.json \
                                    --out TELEMETRY_COMPARE.json
                            else
                                echo "[tpu_capture] compare: no previous" \
                                    "capture — recording baseline only"
                            fi
                            if [ -d artifacts/telemetry_northstar ]; then
                                rm -rf artifacts/telemetry_northstar_prev
                                cp -r artifacts/telemetry_northstar \
                                    artifacts/telemetry_northstar_prev
                            fi
                        fi ;;
        conv-ab)        run env BENCH_CONV_IMPL=matmul python bench.py
                        run env BENCH_CONV_IMPL=conv python bench.py ;;
        zoo)            run python scripts/tpu_zoo_check.py ;;
        pallas)         run python scripts/pallas_tpu_check.py ;;
        flash-train)    run python scripts/flash_train_bench.py ;;
        flash-sweep)    run python scripts/flash_block_sweep.py ;;
        vmap)           run python scripts/vmap_penalty_bench.py ;;
        mfu)            run env MFU_PROFILE=1 python scripts/mfu_sweep.py
                        # pipe the armed on-chip traces straight through
                        # the attributor: the capture yields the
                        # category table in the same command
                        run python -m fedtorch_tpu.tools.trace_attrib \
                            artifacts/trace_northstar \
                            --out artifacts/attrib_northstar.json \
                            --render artifacts/attrib_northstar.txt
                        run python -m fedtorch_tpu.tools.trace_attrib \
                            artifacts/trace_northstar_fused \
                            --out artifacts/attrib_northstar_fused.json \
                            --render artifacts/attrib_northstar_fused.txt ;;
        moe)            run python scripts/moe_ab_bench.py ;;
        seqpar)         run python scripts/seqpar_tpu_probe.py ;;
        baseline)       run python scripts/baseline_suite.py ;;
        curves)         run python scripts/northstar_synthetic.py ;;
        audit)          run python -m fedtorch_tpu.lint --audit \
                            --out PROGRAM_AUDIT.json ;;
        concurrency)    run python -m fedtorch_tpu.lint --concurrency \
                            --out CONCURRENCY_AUDIT.json ;;
        *) echo "[tpu_capture] unknown step: $step"; FAILED=1 ;;
    esac
done
echo "[tpu_capture] done (failed=$FAILED)"
exit $FAILED
