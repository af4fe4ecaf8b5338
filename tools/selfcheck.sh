#!/usr/bin/env bash
# selfcheck — CI gate: the static-analysis battery over the entire
# model zoo and runtime tree, plus the dynamic smoke/chaos sweeps.
#
# Stage 0 runs `tools/lintall.py --json`: EVERY static gate in ONE
# process — racelint (concurrency, docs/RELIABILITY.md "Static
# concurrency checking"), fluidlint --all-models (IR verifier over
# the zoo), numlint --all-models plain AND under --amp O2 (numerics),
# and protolint (distributed-fabric contracts, "Static protocol
# checking") — exit 1 on ANY unsuppressed error-level finding in any
# gate. Warnings are reported but never fail. Pure host-CPU static
# analysis, one aggregated JSON verdict ($OUT/lintall.json, per-gate
# docs alongside). The PR-12 teeth fixture keeps stage 0 honest; the
# numerics and protocol teeth fixtures live in stages 11 and 15.
#
# Stage 2 runs `tools/faultsmoke.py`: one crash/resume cycle on a zoo
# model through the crash-safe checkpoint store (torn write injected
# mid-save, recovery from the newest verified serial) — the resilience
# subsystem's end-to-end gate (docs/RELIABILITY.md).
#
# Stage 3 runs `tools/servebench.py`: the serving subsystem's smoke
# (docs/SERVING.md) — a tiny zoo model behind the batching engine must
# beat the single-request baseline (--assert-speedup 1.2, deliberately
# below the ~2-3x typically measured so a loaded CI host doesn't
# flake) with zero correctness drops and zero post-warmup recompiles
# (servebench exits 1 on any of those).
#
# Stage 4 runs `tools/servebench.py --chaos`: the serving-hardening
# drill (docs/SERVING.md "Operating under failure") — device faults
# injected mid-load must lose ZERO requests (every submission ends in
# a result or a typed error), the circuit breaker must open and then
# recover, and close(drain=True) must complete all in-flight work.
#
# Usage: tools/selfcheck.sh [output-dir]
set -u -o pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

OUT="${1:-/tmp/fluidlint}"
mkdir -p "$OUT"

models=$(python tools/fluidlint.py --list) || {
    echo "selfcheck: failed to enumerate the model zoo" >&2; exit 1; }

# ---- stage 0: the whole static battery, one process (lintall) --------
# racelint + fluidlint --all-models + numlint (plain, --amp O2) +
# protolint, aggregated; each gate's own JSON lands in $OUT/<gate>.json
if python tools/lintall.py --json --out "$OUT" \
        > "$OUT/lintall.json" 2> "$OUT/lintall.err"; then
    summary=$(python - "$OUT/lintall.json" <<'EOF0'
import json, sys
d = json.load(open(sys.argv[1]))
for name, g in d["gates"].items():
    print(f"ok   {name:15s} {g['summary']}")
EOF0
    )
    echo "$summary"
else
    echo "FAIL lintall — see $OUT/lintall.json / $OUT/lintall.err" >&2
    exit 1
fi
# the gate must have teeth: the jarred PR-12 scope bug still fails it
if python tools/racelint.py --json \
        tests/fixtures/racecheck_pr12_scope_bug.py \
        > "$OUT/racelint_pr12.json" 2>&1; then
    echo "FAIL racelint let the PR-12 scope-bug fixture pass — the" \
         "concurrency gate is toothless" >&2
    exit 1
else
    echo "ok   racelint rejects the PR-12 regression fixture"
fi
echo "selfcheck: static battery passed (racelint + fluidlint +" \
     "numlint + numlint/amp + protolint in one process)"

# ---- stage 2: fault-injection smoke (crash/resume cycle) -------------
if python tools/faultsmoke.py --dir "$OUT/faultsmoke" \
        > "$OUT/faultsmoke.log" 2>&1; then
    echo "ok   faultsmoke ($(tail -1 "$OUT/faultsmoke.log"))"
else
    echo "FAIL faultsmoke — see $OUT/faultsmoke.log" >&2
    exit 1
fi
echo "selfcheck: fault-injection smoke passed"

# ---- stage 3: serving smoke (batched > single-request, exact) --------
if python tools/servebench.py --model mnist_mlp --requests 96 \
        --assert-speedup 1.2 --out "$OUT/servebench.json" \
        > "$OUT/servebench.log" 2>&1; then
    echo "ok   servebench ($(tail -1 "$OUT/servebench.log"))"
else
    echo "FAIL servebench — see $OUT/servebench.log / servebench.json" >&2
    exit 1
fi
echo "selfcheck: serving smoke passed"

# ---- stage 4: serving chaos drill (no lost requests under faults) ----
if python tools/servebench.py --chaos --model mnist_mlp --requests 64 \
        --out "$OUT/servebench_chaos.json" \
        > "$OUT/servebench_chaos.log" 2>&1; then
    echo "ok   servebench --chaos ($(tail -1 "$OUT/servebench_chaos.log"))"
else
    echo "FAIL servebench --chaos — see $OUT/servebench_chaos.log /" \
         "servebench_chaos.json" >&2
    exit 1
fi
echo "selfcheck: serving chaos drill passed"

# ---- stage 5: static cost report sweep + rewrite-equivalence gate ----
# `fluidlint --report --json` must produce the cost/residency document
# (now incl. rewrite-pipeline stats) for EVERY zoo model, and
# `optcheck` proves Program.optimize() is bit-exact on one model —
# each rewrite pass in isolation (fold, fuse) and the full pipeline
# in combination.
fail=0
for m in $models; do
    if python tools/fluidlint.py --model "$m" --report --json \
            > "$OUT/${m}_report.json" 2>> "$OUT/$m.err"; then
        summary=$(python - "$OUT/${m}_report.json" <<'EOF2'
import json, sys
d = json.load(open(sys.argv[1]))
r = d.get("report") or {}
assert r.get("peak_residency_bytes", 0) > 0, "missing peak residency"
assert r.get("top_ops"), "missing per-op costs"
print(f"peak {r['peak_residency_bytes']/2**20:.2f} MiB, "
      f"{r['dead_op_count']} dead, remat {r['recommended_remat_policy']}")
EOF2
        ) || { echo "FAIL $m --report (incomplete cost doc)" >&2; fail=1; continue; }
        echo "ok   $m --report ($summary)"
    else
        echo "FAIL $m --report — see $OUT/${m}_report.json / $OUT/$m.err" >&2
        fail=1
    fi
done
if [ "$fail" -ne 0 ]; then
    echo "selfcheck: cost report sweep failed" >&2
    exit 1
fi

rm -f "$OUT/optcheck.log"
for p in fold fuse fold,fuse,cse,dce; do
    if python tools/optcheck.py --model mnist_mlp --passes "$p" \
            >> "$OUT/optcheck.log" 2>&1; then
        echo "ok   optcheck --passes $p ($(tail -1 "$OUT/optcheck.log"))"
    else
        echo "FAIL optcheck --passes $p — see $OUT/optcheck.log" >&2
        exit 1
    fi
done
if python tools/optcheck.py --model mnist_mlp \
        >> "$OUT/optcheck.log" 2>&1; then
    echo "ok   optcheck ($(tail -1 "$OUT/optcheck.log"))"
else
    echo "FAIL optcheck — see $OUT/optcheck.log" >&2
    exit 1
fi
# layout-conversion gate on a conv model: the opt-in NCHW->NHWC pass in
# isolation and combined with the default pipeline (bit-exact on
# transpose-only paths, documented tight tolerance + run-to-run
# stability on converted conv paths — optcheck enforces the split)
for p in layout layout,fold,fuse,cse,dce; do
    if python tools/optcheck.py --model mnist --passes "$p" \
            >> "$OUT/optcheck.log" 2>&1; then
        echo "ok   optcheck --passes $p ($(tail -1 "$OUT/optcheck.log"))"
    else
        echo "FAIL optcheck --passes $p — see $OUT/optcheck.log" >&2
        exit 1
    fi
done
echo "selfcheck: static cost sweep + rewrite-equivalence gate passed"

# ---- stage 6: continuous-batching decode smoke -----------------------
# Tiny-config llama through the paged-KV decode engine
# (docs/SERVING.md "Continuous decode batching"): servebench --decode
# exits 1 unless tok/s > 0, every request's greedy tokens match the
# sequential fused-generator baseline exactly, and ZERO XLA compiles
# happen after warmup while requests churn through the slots. The
# closed-loop speedup race lives in the bench ladder, not here (a
# loaded CI host would flake it); this gate pins correctness + the
# no-recompile contract.
if python tools/servebench.py --decode --requests 16 --max-new 16 \
        --out "$OUT/servebench_decode.json" \
        > "$OUT/servebench_decode.log" 2>&1; then
    echo "ok   servebench --decode ($(tail -1 "$OUT/servebench_decode.log"))"
else
    echo "FAIL servebench --decode — see $OUT/servebench_decode.log /" \
         "servebench_decode.json" >&2
    exit 1
fi
echo "selfcheck: decode serving smoke passed"

# ---- stage 7: replica-pool router smoke ------------------------------
# The cluster subsystem's gate (docs/SERVING.md "Running a replica
# pool"): 2 replicas behind the health-aware router take mixed 1- and
# 2-row traffic while every replica is drained + rebuilt one at a
# time (rolling_restart). servebench exits 1 if ANY request is lost
# or surfaces a typed error during the roll, if the pool ever reports
# fewer than N-1 READY replicas, if pool results diverge from a lone
# engine's, or if the pool serves less of the burst-overload trace
# than one engine (the capacity win that holds on any host — the
# parallel-compute speedup race would flake on a 1-core CI box).
if python tools/servebench.py --cluster 2 --rolling-restart \
        --requests 48 --concurrency 8 \
        --out "$OUT/servebench_cluster.json" \
        > "$OUT/servebench_cluster.log" 2>&1; then
    echo "ok   servebench --cluster ($(tail -1 "$OUT/servebench_cluster.log"))"
else
    echo "FAIL servebench --cluster — see $OUT/servebench_cluster.log /" \
         "servebench_cluster.json" >&2
    exit 1
fi
# replica-crash chaos through the pool: a replica is killed mid-load
# (serving_replica_crash), the router reroutes + fails over with zero
# losses, and the pool's monitor revives the corpse.
if python tools/servebench.py --chaos --cluster 2 --requests 24 \
        --concurrency 8 \
        --out "$OUT/servebench_cluster_chaos.json" \
        > "$OUT/servebench_cluster_chaos.log" 2>&1; then
    echo "ok   servebench --chaos --cluster" \
         "($(tail -1 "$OUT/servebench_cluster_chaos.log"))"
else
    echo "FAIL servebench --chaos --cluster — see" \
         "$OUT/servebench_cluster_chaos.log /" \
         "servebench_cluster_chaos.json" >&2
    exit 1
fi
echo "selfcheck: replica-pool router smoke passed"

# (stage 8, the compiled-artifact store's gate, went with the store.)

# ---- stage 9: cross-host serving fabric (sockets + partitions) -------
# The network fabric's gate (docs/DISTRIBUTED.md "Serving across
# hosts"): servebench --remote 2 stands up loopback ReplicaServers
# from one exported dir — one of them provisioned purely OVER THE
# SOCKET (fetch_manifest/fetch_artifact, sha256-verified) — and exits
# 1 unless the socket pool serves every request within float
# tolerance of a local engine. Then the partition chaos drill:
# net_partition + net_frame_drop armed mid-load must lose ZERO
# requests (typed errors only), open and re-close the per-connection
# breakers, and rejoin the partitioned replicas within one membership
# refresh of the fault clearing.
if python tools/servebench.py --remote 2 --requests 48 \
        --concurrency 8 --out "$OUT/servebench_remote.json" \
        > "$OUT/servebench_remote.log" 2>&1; then
    echo "ok   servebench --remote ($(tail -1 "$OUT/servebench_remote.log"))"
else
    echo "FAIL servebench --remote — see $OUT/servebench_remote.log /" \
         "servebench_remote.json" >&2
    exit 1
fi
if python tools/servebench.py --chaos --remote 2 --requests 24 \
        --concurrency 8 --out "$OUT/servebench_remote_chaos.json" \
        > "$OUT/servebench_remote_chaos.log" 2>&1; then
    echo "ok   servebench --chaos --remote" \
         "($(tail -1 "$OUT/servebench_remote_chaos.log"))"
else
    echo "FAIL servebench --chaos --remote — see" \
         "$OUT/servebench_remote_chaos.log /" \
         "servebench_remote_chaos.json" >&2
    exit 1
fi
echo "selfcheck: cross-host serving fabric gate passed"

# ---- stage 10: versioned-deployment canary drill ---------------------
# The deployment loop's gate (docs/SERVING.md "Deploying a new
# version"): servebench --canary exports two versions, dark-deploys
# v2 behind router weights, proves the golden-set numerics gate
# ACCEPTS a faithful canary, then arms serving_canary_regression and
# exits 1 unless the staged promotion auto-REJECTS on the in-flight
# numerics resample and rolls back to v1 with zero lost requests and
# zero typed errors. Records serving_rollback_s.
if python tools/servebench.py --canary --requests 48 \
        --concurrency 8 --out "$OUT/servebench_canary.json" \
        > "$OUT/servebench_canary.log" 2>&1; then
    echo "ok   servebench --canary ($(tail -1 "$OUT/servebench_canary.log"))"
else
    echo "FAIL servebench --canary — see $OUT/servebench_canary.log /" \
         "servebench_canary.json" >&2
    exit 1
fi
echo "selfcheck: versioned-deployment canary gate passed"

# ---- stage 11: static numerics gate teeth (numcheck) -----------------
# The numerics analyzer's gate (docs/RELIABILITY.md "Static numerics
# checking"). The clean-zoo sweeps — plain AND under `--amp O2` —
# already ran inside stage 0's lintall; this stage proves the gate
# has teeth: seeded fp16-overflow and int8-scale-clip fixture
# programs must FAIL the lint (exit 1 with the expected code). Then
# optcheck re-proves the rewrite passes the pipeline previously
# refused wholesale under AMP: fold+fuse held to bit-exact, the
# layout chain to the documented AMP tolerance tier
# (docs/PERFORMANCE.md §9d).
# the gate must have teeth: seeded hazard fixtures must fail the lint
rm -rf "$OUT/numcheck_fixtures"; mkdir -p "$OUT/numcheck_fixtures"
if python - "$OUT/numcheck_fixtures" > "$OUT/numcheck_fixtures.log" 2>&1 <<'EOF11F'
import sys, os
import paddle_tpu as fluid

fluid.force_cpu()
out_dir = sys.argv[1]

def build(hazard):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.sigmoid(x)           # provably [0, 1]
        out = hazard(y)
    return main, out.name

for name, hazard in [
    ("fp16_overflow", lambda y: fluid.layers.cast(
        fluid.layers.scale(y, scale=1e6), dtype="float16")),
    ("int8_clip", lambda y: fluid.layers.cast(
        fluid.layers.scale(y, scale=300.0), dtype="int8")),
]:
    main, fetch = build(hazard)
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        f.write(main.to_json())
    with open(os.path.join(out_dir, name + ".fetch"), "w") as f:
        f.write(fetch)
print("fixtures seeded")
EOF11F
then
    echo "ok   numcheck hazard fixtures seeded"
else
    echo "FAIL numcheck fixture seeding — see $OUT/numcheck_fixtures.log" >&2
    exit 1
fi
for fx in fp16_overflow int8_clip; do
    fetch=$(cat "$OUT/numcheck_fixtures/$fx.fetch")
    if python tools/numlint.py --program "$OUT/numcheck_fixtures/$fx.json" \
            --fetch "$fetch" --json \
            > "$OUT/numlint_$fx.json" 2>&1; then
        echo "FAIL numlint let the $fx fixture pass — the numerics gate" \
             "is toothless" >&2
        exit 1
    else
        echo "ok   numlint rejects the $fx fixture"
    fi
done
# AMP rewrite admission: the configs wholesale-refused before numcheck
rm -f "$OUT/optcheck_amp.log"
for spec in "mnist_mlp fold,fuse,cse,dce" "mnist layout,fold,fuse,cse,dce"; do
    set -- $spec
    if python tools/optcheck.py --model "$1" --passes "$2" --amp O2 \
            >> "$OUT/optcheck_amp.log" 2>&1; then
        echo "ok   optcheck --model $1 --passes $2 --amp O2" \
             "($(tail -1 "$OUT/optcheck_amp.log"))"
    else
        echo "FAIL optcheck --model $1 --passes $2 --amp O2 — see" \
             "$OUT/optcheck_amp.log" >&2
        exit 1
    fi
done
echo "selfcheck: static numerics gate passed"

# ---- stage 12: elastic training-fabric chaos drill -------------------
# The training fabric's gate (docs/DISTRIBUTED.md "Training across
# hosts"): trainbench --chaos runs REAL subprocess workers and fires
# all four trainer fault points against one run — a hard worker crash
# (os._exit mid-step) with an elastic replacement folded back in, a
# straggler evicted typed at the deadline and rejoined after
# healing, a two-call net partition, and a coordinator crash resumed
# by a NEW coordinator from the last committed serial. PASS requires
# the chaos run's committed (serial, sha) sequence to EQUAL the
# uninterrupted reference run's — zero lost committed steps AND
# bit-deterministic resume — plus loss-curve parity. Records
# train_recover_s / train_elastic_resume_s.
if python tools/trainbench.py --chaos --task program \
        --out "$OUT/trainbench_chaos.json" \
        > "$OUT/trainbench_chaos.log" 2>&1; then
    echo "ok   trainbench --chaos ($(tail -1 "$OUT/trainbench_chaos.log"))"
else
    echo "FAIL trainbench --chaos — see $OUT/trainbench_chaos.log /" \
         "trainbench_chaos.json" >&2
    exit 1
fi
# the gate must have teeth: with elasticity OFF the same drill must
# FAIL (a worker crash is then fatal) — proving the assertions above
# actually detect lost runs
if python tools/trainbench.py --chaos --task linreg --no-recover \
        > "$OUT/trainbench_norecover.log" 2>&1; then
    echo "FAIL trainbench --chaos --no-recover PASSED — the elastic" \
         "gate is toothless" >&2
    exit 1
else
    echo "ok   trainbench --chaos --no-recover fails as it must" \
         "($(tail -1 "$OUT/trainbench_norecover.log"))"
fi
echo "selfcheck: elastic training-fabric gate passed"

# ---- stage 13: SLO-aware disaggregated decode serving ----------------
# The disaggregated-serving gate (docs/SERVING.md "Disaggregated
# decode serving"): servebench --decode --slo runs a mixed short/long
# interference trace three ways — FIFO admission, the EDF SLO
# scheduler, and a 2-prefill/2-decode disaggregated pool behind
# Router.generate — and exits 1 unless the SLO scheduler's TTFT
# attainment is STRICTLY better than FIFO's (the interactive target is
# calibrated to a quarter of FIFO's measured queue-wait TTFT, so the
# comparison is scheduling-order-driven on any CPU speed), every arm
# decodes bit-identical greedy tokens, zero XLA compiles happen after
# warmup, and the serving_handoff_drop chaos drill (a prefill replica
# dies holding the finished KV blob mid-handoff) completes every
# request via re-prefill on the survivor.
if python tools/servebench.py --decode --slo \
        --out "$OUT/servebench_slo.json" \
        > "$OUT/servebench_slo.log" 2>&1; then
    echo "ok   servebench --decode --slo" \
         "($(tail -1 "$OUT/servebench_slo.log"))"
else
    echo "FAIL servebench --decode --slo — see $OUT/servebench_slo.log" \
         "/ servebench_slo.json" >&2
    exit 1
fi
# the gate must have teeth: with the comparison arm forced onto the
# FIFO scheduler the attainment cannot be strictly better, so the
# same drill must FAIL — proving the gate detects a scheduler that
# does nothing
if python tools/servebench.py --decode --slo --slo-force-fifo \
        --skip-disagg > "$OUT/servebench_slo_forced.log" 2>&1; then
    echo "FAIL servebench --decode --slo --slo-force-fifo PASSED —" \
         "the SLO-attainment gate is toothless" >&2
    exit 1
else
    echo "ok   servebench --slo --slo-force-fifo fails as it must"
fi
echo "selfcheck: disaggregated SLO serving gate passed"

# ---- stage 14: graceful degradation at the overload knee -------------
# The overload-robustness gate (docs/RELIABILITY.md "Operating at the
# overload knee"): servebench --overload replays the shipped diurnal/
# flash-crowd trace (tools/traces/diurnal_flashcrowd.json) through a
# rate ladder to MEASURE the pool's knee, then drills at 3x that knee
# on the full graceful stack — SLO/EDF scheduling, AIMD adaptive
# admission under the fixed hard ceiling, the brownout ladder, and a
# retry budget — and exits 1 unless the flash crowd sheds ZERO
# interactive requests while batch sheds, every brownout engage is
# matched by a revert (final levels 0), the serving_retry_storm drill
# stays within its budget and fails fast typed beyond it, and the
# priority-weighted goodput beats a flat-FIFO/fixed-bound baseline at
# the same offered load. Records serving_overload_knee_qps and
# serving_overload_goodput_ratio.
OVERLOAD_FLAGS="--trace-file tools/traces/diurnal_flashcrowd.json \
    --rate 3 --ladder-growth 2 --ladder-rungs 4 --max-batch 4 \
    --max-new 96 --decode-block 1 --request-timeout 8"
if python tools/servebench.py --overload $OVERLOAD_FLAGS \
        --out "$OUT/servebench_overload.json" \
        > "$OUT/servebench_overload.log" 2>&1; then
    echo "ok   servebench --overload" \
         "($(tail -1 "$OUT/servebench_overload.log"))"
else
    echo "FAIL servebench --overload — see" \
         "$OUT/servebench_overload.log / servebench_overload.json" >&2
    exit 1
fi
# the gate must have teeth: the SAME drill with every overload control
# stripped (--overload-flat-shed: FIFO admission, fixed bound only, no
# brownout, no retry budget) must FAIL — interactive sheds with the
# rest, the storm retries unbounded — proving the assertions above
# detect a stack that degrades ungracefully
if python tools/servebench.py --overload --overload-flat-shed \
        $OVERLOAD_FLAGS > "$OUT/servebench_overload_flat.log" 2>&1; then
    echo "FAIL servebench --overload --overload-flat-shed PASSED —" \
         "the overload gate is toothless" >&2
    exit 1
else
    echo "ok   servebench --overload --overload-flat-shed fails as" \
         "it must"
fi
echo "selfcheck: overload-knee gate passed"

# ---- stage 15: static protocol gate (protocheck) ---------------------
# The fabric-contract analyzer's gate (docs/RELIABILITY.md "Static
# protocol checking"). The clean-tree sweep already ran inside stage
# 0's lintall; this stage (a) re-runs the standalone gate so a
# lintall wiring bug can't mask it, (b) proves the gate has teeth —
# the jarred unregistered-wire-error + unknown-fault-point fixture
# must FAIL — and (c) diffs the knob table committed in
# docs/RELIABILITY.md against a fresh --knobs-table render, so the
# PADDLE_TPU_* reference can never drift from the tree.
if python tools/protolint.py --json > "$OUT/protolint.json" \
        2> "$OUT/protolint.err"; then
    summary=$(python - "$OUT/protolint.json" <<'EOF15'
import json, sys
d = json.load(open(sys.argv[1]))
print(f"{d['files']} files, {d['error_count']} errors, "
      f"{len(d['suppressed'])} suppressed, {len(d['knobs'])} knobs")
EOF15
    )
    echo "ok   protolint ($summary)"
else
    echo "FAIL protolint — see $OUT/protolint.json /" \
         "$OUT/protolint.err" >&2
    exit 1
fi
if python tools/protolint.py --json tests/fixtures/protocheck_teeth.py \
        > "$OUT/protolint_teeth.json" 2>&1; then
    echo "FAIL protolint let the protocol teeth fixture pass — the" \
         "protocol gate is toothless" >&2
    exit 1
else
    echo "ok   protolint rejects the protocol teeth fixture"
fi
if python - > "$OUT/protolint_knobs.log" 2>&1 <<'EOF15K'
import sys
from paddle_tpu.analysis import protocheck
report = protocheck.run_tree()
fresh = protocheck.render_knobs_table(report.knobs)
text = open("docs/RELIABILITY.md", encoding="utf-8").read()
b = text.find(protocheck.KNOBS_BEGIN)
e = text.find(protocheck.KNOBS_END)
if b < 0 or e < 0:
    print("knob-table markers missing from docs/RELIABILITY.md")
    sys.exit(1)
committed = text[b:e + len(protocheck.KNOBS_END)]
if committed.strip() != fresh.strip():
    print("docs/RELIABILITY.md knob table drifted from the tree —")
    print("regenerate: python tools/protolint.py --knobs-table")
    sys.exit(1)
print(f"{len(report.knobs)} knob(s), committed table in sync")
EOF15K
then
    echo "ok   knob table in docs/RELIABILITY.md matches the tree" \
         "($(tail -1 "$OUT/protolint_knobs.log"))"
else
    echo "FAIL knob-table drift — see $OUT/protolint_knobs.log" >&2
    exit 1
fi
echo "selfcheck: static protocol gate passed"
