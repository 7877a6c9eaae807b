#!/bin/bash
# Full validation matrix (the reference's paddle_build.sh ctest+py_test
# role).  Runs everywhere: tests force a virtual 8-device CPU mesh.
set -e
cd "$(dirname "$0")/.."

echo "== 1/8 test suite (virtual 8-device CPU mesh; two lanes) =="
# fast lane first: cheap tests fail the matrix within ~5 min before
# the subprocess-cluster/compile-heavy slow lane spends half an hour.
# Together the lanes are the identical full suite (conftest assigns
# `slow` from tools/test_durations.json).
# a missing/empty manifest marks nothing slow; exit code 5 (nothing
# collected) from the then-empty slow lane must not fail the matrix
python -m pytest tests/ -q -m "not slow"
python -m pytest tests/ -q -m "slow" || { rc=$?; [ "$rc" -eq 5 ]; }

echo "== 1b/8 repo-discipline lint (tools/repo_lint.py) =="
# ISSUE 15: the written disciplines (flags default off, ServingError
# subclasses carry stable codes, metric-name grammar, registered
# faultinject msg types, documented PADDLE_TPU_* knobs, no bare
# except) are AST-enforced; intentional exceptions live in
# tools/repo_lint_allowlist.json with a one-line reason each, and a
# stale allowlist entry is itself a failure (docs/ANALYSIS.md)
python tools/repo_lint.py --json > /tmp/_repo_lint.json
cat /tmp/_repo_lint.json
python - <<'PY'
import json
lines = [ln for ln in open("/tmp/_repo_lint.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, "repo_lint stdout must be ONE JSON line"
rec = json.loads(lines[0])
assert rec["metric"] == "repo_lint"
assert rec["ok"] is True, (
    "repo discipline violated: %r" % rec["findings"])
print("repo_lint OK: 0 findings, %d allowlisted" % rec["allowed"])
PY

echo "== 2/8 op inventory audit vs reference REGISTER_OPERATOR =="
JAX_PLATFORMS=cpu python tools/op_coverage.py

echo "== 3/8 API stability gate =="
JAX_PLATFORMS=cpu python tools/print_signatures.py paddle_tpu > /tmp/_api_now.spec
python tools/diff_api.py API.spec /tmp/_api_now.spec

echo "== 4/8 multichip dry-run (8 virtual devices) =="
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -c "
import __graft_entry__ as ge; ge.dryrun_multichip(8)
print('dryrun_multichip(8) OK')"

echo "== 4b/8 gspmd simulated-hosts smoke (one pjit step, dp x tp mesh) =="
# ISSUE 8: the sharded train step over the virtual mesh partitioned
# into 2 simulated hosts (dryrun_multichip style — this container's
# CPU backend cannot execute true multi-process computations, same
# reason the multihost dp test is environment-gated).  Gates the
# one-JSON-line contract with per-host + global MFU; the same worker
# path runs real jax.distributed fleets on pods.
JAX_PLATFORMS=cpu python tools/bench_multihost.py --mode gspmd \
  --simulate-hosts 2 --devices-per-host 4 --batch-per-host 8 \
  --steps 3 --warmup 1 > /tmp/_gspmd_smoke.json
cat /tmp/_gspmd_smoke.json
python - <<'PY'
import json
lines = [ln for ln in open("/tmp/_gspmd_smoke.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, (
    "gspmd smoke stdout must be exactly ONE JSON line — got %d"
    % len(lines))
rec = json.loads(lines[0])
missing = {"metric", "value", "unit", "mfu_pct", "tokens_per_sec",
           "hosts", "dp", "tp", "per_host", "loss"} - set(rec)
assert not missing, "gspmd smoke JSON missing fields: %s" % (
    sorted(missing),)
assert rec["metric"] == "multihost_gspmd_train"
assert len(rec["per_host"]) == rec["hosts"] == 2
assert all("host_mfu_pct" in h for h in rec["per_host"])
import math
assert math.isfinite(rec["loss"]), rec["loss"]
print("gspmd smoke OK: dp=%s tp=%s mfu=%s%%"
      % (rec["dp"], rec["tp"], rec["mfu_pct"]))
PY

echo "== 5/8 no chip, no number (benchmarks/run.py and chip_smoke.py refuse the CPU) =="
# this matrix runs on the CPU.  The benchmark and the chip smoke
# measure on the chip or not at all: without one each must exit
# non-zero before it runs anything, print no result and write nothing
# under docs/.  On the chip: `python chip_smoke.py` (through the chip
# tool from a sandbox; docs/GETTING_STARTED.md).
docs_before="$(git status --porcelain docs/)"
if JAX_PLATFORMS=cpu python benchmarks/run.py \
    --workload tfm_base_train_s512 --seed 1 --seconds 1 --trace 0 \
    > /tmp/_bench_stdout.json 2> /dev/null; then
  echo "benchmarks/run.py ran without a chip"; exit 1
fi
if JAX_PLATFORMS=cpu python chip_smoke.py > /tmp/_smoke_stdout.json \
    2> /dev/null; then
  echo "chip_smoke.py ran without a chip"; exit 1
fi
[ ! -s /tmp/_bench_stdout.json ] && [ ! -s /tmp/_smoke_stdout.json ]
[ "$docs_before" = "$(git status --porcelain docs/)" ]
echo "no-chip refusal OK"

echo "== 5b/8 serving load generator (one-JSON-line contract) =="
# stdout contract: the driver/soak parse this as ONE
# JSON line; a short fixed-rate leg proves the generator + server
# round-trip and the headline fields (docs/SERVING.md)
JAX_PLATFORMS=cpu python tools/serving_load.py --seconds 1.5 \
  --qps 150 --seed 7 > /tmp/_serving_load.json
cat /tmp/_serving_load.json
python - <<'PY'
import json
lines = [ln for ln in open("/tmp/_serving_load.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, (
    "serving_load.py stdout must be exactly ONE JSON line — got %d"
    % len(lines))
rec = json.loads(lines[0])
missing = {"metric", "value", "unit", "offered_qps", "goodput_qps",
           "p50_ms", "p99_ms", "admitted", "ok", "shed", "expired",
           "failed_over", "accounted", "seed", "mode",
           "metrics", "slo"} - set(rec)
assert not missing, "serving_load JSON missing fields: %s" % (
    sorted(missing),)
assert rec["accounted"] is True, "request accounting broken: %r" % rec
# ISSUE 9: the embedded metrics-registry snapshot must parse and
# carry the admission instrument with a nonzero admitted series
m = rec["metrics"]
assert isinstance(m, dict) and \
    "paddle_tpu_admission_requests_total" in m, sorted(m)[:10]
adm = m["paddle_tpu_admission_requests_total"]["series"]
admitted = sum(s["value"] for s in adm
               if s["labels"].get("outcome") == "admitted")
assert admitted > 0, adm
# ISSUE 10: the slo embed must carry the availability objective with
# the per-objective {attained, target, burn_rate} shape
slo = rec["slo"]
assert isinstance(slo, dict) and "serving_availability" in slo, \
    sorted(slo)
avail = slo["serving_availability"]
assert {"attained", "target", "burn_rate", "firing"} <= set(avail), \
    avail
assert avail["target"] == 0.99, avail
print("serving_load stdout contract OK: 1 line, %d fields, "
      "%d instruments in metrics snapshot, %d slo objectives"
      % (len(rec), len(m), len(slo)))
PY

# decode act II leg (ISSUE 11): one short decode-mode run with all
# three flags on — the one-JSON-line contract grows acceptance_rate /
# prefix-sharing / chunked-prefill evidence and the generalized
# zero-leak verdict; a generous deadline keeps the CPU run honest
# (the spec path compiles several extra shapes in its first second)
JAX_PLATFORMS=cpu python tools/serving_load.py --mode decode \
  --seconds 2 --qps 30 --seed 7 --deadline-ms 5000 \
  --spec-k 2 --prefix-shared 32 --prefill-chunk 8 \
  > /tmp/_serving_load_decode.json
cat /tmp/_serving_load_decode.json
python - <<'PY'
import json
lines = [ln for ln in
         open("/tmp/_serving_load_decode.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, (
    "serving_load --mode decode stdout must be exactly ONE JSON line "
    "— got %d" % len(lines))
rec = json.loads(lines[0])
missing = {"metric", "value", "unit", "tokens_per_sec",
           "inter_token_p99_ms", "acceptance_rate", "spec_k",
           "prefix_shared", "peak_shared_pages", "prefill_chunk",
           "prefill_chunks", "pages_accounted", "accounted",
           "metrics", "slo"} - set(rec)
assert not missing, "decode JSON missing fields: %s" % (
    sorted(missing),)
assert rec["metric"] == "decode_tokens_per_sec", rec["metric"]
assert rec["accounted"] is True, rec
assert rec["pages_accounted"] is True, (
    "generalized zero-leak invariant broken: %r" % rec)
assert rec["spec_k"] == 2 and rec["prefix_shared"] == 32
assert rec["ok"] > 0, "no decode request ever succeeded: %r" % rec
assert rec["prefill_chunks"] > 0, "chunked prefill never ran"
# the paged-KV page-pressure gauges ride the metrics embed
m = rec["metrics"]
for g in ("paddle_tpu_paged_kv_pages_free",
          "paddle_tpu_paged_kv_pages_in_use",
          "paddle_tpu_paged_kv_pages_shared"):
    assert g in m, (g, sorted(m)[:12])
print("decode act-II contract OK: %.1f tok/s, acceptance %.4f, "
      "%d peak shared pages, %d chunks"
      % (rec["tokens_per_sec"], rec["acceptance_rate"],
         rec["peak_shared_pages"], rec["prefill_chunks"]))
PY

echo "== 5c/8 observability smoke (tracing on: one trace id end-to-end) =="
# ISSUE 9 acceptance gate: with the tracing flag on, a seeded serving
# round-trip and a decode sequence each carry ONE trace id across
# every stage (submit->admission->batch->replica->Predictor.run->
# delivery; join->step->retire), the pserver-side handler span joins
# the client's trace via the RPC envelope, and the /metrics exposition
# parses under the in-tree prometheus grammar check (no external dep).
JAX_PLATFORMS=cpu python tools/observability_smoke.py \
  > /tmp/_obs_smoke.json
cat /tmp/_obs_smoke.json
python - <<'PY'
import json
lines = [ln for ln in open("/tmp/_obs_smoke.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, (
    "observability smoke stdout must be exactly ONE JSON line — got "
    "%d" % len(lines))
rec = json.loads(lines[0])
for k in ("serving_trace_ok", "decode_trace_ok", "rpc_trace_joined",
          "prometheus_ok", "flight_ok",
          # ISSUE 10: device-time attribution (CPU DeviceTraceSession
          # join), head-based sampling accounting, /sloz
          "device_trace_ok", "sampling_ok", "sloz_ok",
          # ISSUE 12: exemplar-bearing exposition validates end to
          # end; two processes assemble one trace in the collector
          # and /fleetz parses
          "exemplar_ok", "collector_ok"):
    assert rec.get(k) is True, (k, rec)
assert rec["serving_trace_id"] and rec["decode_trace_id"]
assert rec["exemplars"] >= 1 and rec["fleet_trace_id"]
s = rec["sampling"]
assert s["sampled"] + s["dropped"] == s["offered"], s
print("observability smoke OK: serving trace %s, decode trace %s, "
      "%d prom samples, %d device slices joined, sampling %d/%d, "
      "%d exemplars, fleet trace %s"
      % (rec["serving_trace_id"], rec["decode_trace_id"],
         rec["prom_samples"], rec["device_joined_slices"],
         s["sampled"], s["offered"], rec["exemplars"],
         rec["fleet_trace_id"]))
PY

echo "== 5d/8 tail-latency forensics gate (seeded overload attribution) =="
# ISSUE 12: a seeded 2x-overload run with tracing head-sampled at 0.5
# must decompose its slowest traces into the stage breakdown with
# segment sums closing over each span's wall time, and the aggregate
# attribution must provably name admission-queue wait — the automated
# answer to "where does the p99 go?"
JAX_PLATFORMS=cpu python tools/tail_forensics.py --run \
  --seconds 2 --seed 7 --sample 0.5 --slowest 5 \
  > /tmp/_forensics.json
cat /tmp/_forensics.json
python - <<'PY'
import json
lines = [ln for ln in open("/tmp/_forensics.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, (
    "tail_forensics stdout must be exactly ONE JSON line — got %d"
    % len(lines))
rec = json.loads(lines[0])
missing = {"metric", "value", "unit", "dominant", "n_traces",
           "aggregate_us", "per_trace", "closure_ok"} - set(rec)
assert not missing, "forensics JSON missing fields: %s" % (
    sorted(missing),)
assert rec["metric"] == "tail_forensics"
assert rec["n_traces"] >= 3, rec["n_traces"]
assert rec["closure_ok"] is True, (
    "segment sums must close over the span wall time: %r"
    % rec["per_trace"])
assert rec["dominant"] == "admission_wait", (
    "overload p99 must be attributed to admission-queue wait, got "
    "%r (%r)" % (rec["dominant"], rec["aggregate_us"]))
print("forensics gate OK: %s dominates at %.1f%% over %d traces"
      % (rec["dominant"], rec["value"], rec["n_traces"]))
PY

echo "== 5f/8 fleet rollout smoke (zero-drop rolling swap + SLO autoscaler) =="
# ISSUE 13: one seeded rollout iteration — a 3-replica fleet serving
# live traffic swaps v1 -> v2 replica-by-replica under a chaos plan
# (kill mid-rollout / dropped health / delays); the one-JSON-line
# verdict must show zero dropped requests and a fleet converged on
# exactly one version (or cleanly rolled back), and the overload leg
# must show the SLO burn-rate signal ACTUATING at least one scale-up
# with no hysteresis flap.  Replayable from the printed seed.
JAX_PLATFORMS=cpu python tools/chaos_soak.py --mode rollout \
  --iterations 1 --seed 2718 --rate 0.05 > /tmp/_rollout_smoke.json
cat /tmp/_rollout_smoke.json
python - <<'PY'
import json
lines = [ln for ln in open("/tmp/_rollout_smoke.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, (
    "rollout smoke stdout must be exactly ONE JSON line — got %d"
    % len(lines))
rec = json.loads(lines[0])
assert rec["ok"] is True, "rollout smoke failed: %r" % rec["failures"]
r = rec["rollout"]
assert r["zero_dropped"] is True, (
    "requests dropped during rollout: %r" % r)
assert r["converged"] + r["rolled_back"] == rec["iterations"], (
    "fleet neither converged nor rolled back every iteration: %r" % r)
assert r["scale_events"] >= 1 and r["autoscaler_actuated"] is True, (
    "SLO burn never actuated the autoscaler: %r" % r)
print("rollout smoke OK: %d converged / %d rolled back, "
      "%d scale events, final v%s"
      % (r["converged"], r["rolled_back"], r["scale_events"],
         r["final_version"]))
PY

echo "== 5g/8 disaggregated serving gate (page-list handoff + zero-leak) =="
# ISSUE 14: one short decode run with the disaggregated prefill tier
# on — the one-JSON-line contract grows the handoff block (offered /
# adopted / lost / latency percentiles) and the verdict must show
# zero in-transit pages at rest and the generalized zero-leak
# invariant holding on the shared pool
JAX_PLATFORMS=cpu python tools/serving_load.py --mode decode \
  --seconds 2 --qps 30 --seed 7 --deadline-ms 5000 \
  --disagg-prefill 2 > /tmp/_serving_load_disagg.json
cat /tmp/_serving_load_disagg.json
python - <<'PY'
import json
lines = [ln for ln in
         open("/tmp/_serving_load_disagg.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, (
    "serving_load --disagg-prefill stdout must be exactly ONE JSON "
    "line — got %d" % len(lines))
rec = json.loads(lines[0])
missing = {"metric", "value", "unit", "tokens_per_sec",
           "disagg_prefill", "handoff", "pages_accounted",
           "accounted", "metrics", "slo"} - set(rec)
assert not missing, "disagg JSON missing fields: %s" % (
    sorted(missing),)
assert rec["disagg_prefill"] is True
h = rec["handoff"]
assert {"offered", "adopted", "lost", "expired", "in_transit_pages",
        "p50_ms", "p99_ms", "prefill_replicas"} <= set(h), h
assert h["adopted"] > 0, "no handoff ever adopted: %r" % h
assert h["in_transit_pages"] == 0, (
    "pages stuck in transit after drain: %r" % h)
assert rec["pages_accounted"] is True, (
    "generalized zero-leak invariant broken (disagg): %r" % rec)
assert rec["accounted"] is True and rec["ok"] > 0, rec
# the handoff instruments ride the metrics embed
m = rec["metrics"]
for g in ("paddle_tpu_disagg_handoffs_total",
          "paddle_tpu_disagg_handoff_seconds",
          "paddle_tpu_paged_kv_pages_in_transit"):
    assert g in m, (g, sorted(m)[:12])
print("disagg serving gate OK: %.1f tok/s, %d/%d handoffs adopted, "
      "0 in transit" % (rec["tokens_per_sec"], h["adopted"],
                        h["offered"]))
PY

echo "== 6/8 per-op regression gate (hot ops vs committed CPU baseline) =="
# 3x tolerance absorbs machine load; catches order-of-magnitude
# per-op regressions (reference op_tester role) before they surface
# in a model bench
python tools/op_bench.py --cpu --suite tools/op_bench_suite.json \
  --baseline tools/op_bench_baseline_cpu.json --tolerance 3.0

echo "== 7/8 TPU compile gate (the chip's compiler, asked without a chip) =="
# interpret-mode tests never run Mosaic's block-mapping checks; this
# compiles the gate programs for a described v5e:2x2 on the CPU (Mosaic
# lowering, VMEM limits, HBM fit of each program).  The suite (step 1)
# already compiles transformer/deepfm/int8 via
# tests/test_tpu_lowering_gate.py, so only the rest run here.
python tools/tpu_lowering_check.py \
  resnet50_train resnet50_train_convbnstats bert_train resnet50_infer \
  resnet50_infer_int8_interlayer vgg16_infer longctx_train \
  llm_decode llm_decode_d64_hp2 llm_decode_int8kv llm_decode_bf16 \
  llm_decode_spec_k4 llm_decode_spec_k8 llm_decode_disagg \
  transformer_train_gspmd serving_tp_sharded

echo "== 7b/8 IR verifier sweep (ir_verify=full over gate workloads) =="
# ISSUE 15: every gate workload builds with the verifier forced to
# "full" — the structural Program/Block/Op verifier plus the static
# shape/dtype check bracket EVERY transpiler pass the build runs, and
# the final program must round-trip through to_bytes/parse_from_bytes
# with an unchanged program_fingerprint.  Zero error diagnostics on
# legal programs is the acceptance bar (docs/ANALYSIS.md); the
# pytest suite (step 1) already soaks level "on" via conftest.
JAX_PLATFORMS=cpu python tools/verifier_sweep.py \
  > /tmp/_verifier_sweep.json
cat /tmp/_verifier_sweep.json
python - <<'PY'
import json
lines = [ln for ln in
         open("/tmp/_verifier_sweep.json").read().splitlines()
         if ln.strip()]
assert len(lines) == 1, "verifier_sweep stdout must be ONE JSON line"
rec = json.loads(lines[0])
assert rec["metric"] == "verifier_sweep" and rec["level"] == "full"
assert rec["ok"] is True, (
    "verifier sweep found broken IR: %r"
    % {k: v["errors"] for k, v in rec["workloads"].items()
       if not v["ok"]})
assert rec["value"] >= 9, (
    "sweep must cover the gate workload families: %r"
    % sorted(rec["workloads"]))
print("verifier sweep OK: %d workloads clean at level=full"
      % rec["value"])
PY

echo "== 8/8 chaos soak (deterministic seed; both transports) =="
# short fault-injection leg of the distributed stack: a seeded random
# plan (replayable from the seed in the verdict line) drops/closes/
# delays/truncates pserver RPCs; the cluster must complete + converge.
# tools/chaos_soak.py --minutes N is the long-soak form for unattended
# runs (docs/FAULT_TOLERANCE.md).
JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --iterations 2 --seed 1234 --transport both
# serving-tier leg of the same soak: seeded faults over the replica
# pool (kill/close/drop/delay at serving_infer/serving_health) with
# exact request-id accounting asserted each iteration
JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --mode serving --iterations 2 --seed 4321 --rate 0.08
# fleet rollout leg (ISSUE 13): rolling version swap + replica kill
# mid-rollout + autoscaler overload, a different seed than the 5f
# smoke so the soak explores a second chaos schedule
JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --mode rollout --iterations 1 --seed 3141 --rate 0.06
# disaggregated-tier leg (ISSUE 14): seeded kill-mid-handoff chaos —
# a prefill replica dies after page allocation / before adoption and
# a decode replica dies right after adoption (pinned rules) plus the
# random schedule; exactly-once + zero page leaks asserted
JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --mode disagg --iterations 2 --seed 2726 --rate 0.05

echo "ALL CHECKS PASSED"
