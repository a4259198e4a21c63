#!/usr/bin/env bash
# Tier-1 verify gate — the ROADMAP.md "Tier-1 verify" command, verbatim,
# so builders and any future CI run the IDENTICAL gate (same timeout, same
# marker filter, same DOTS_PASSED count). Run from the repo root:
#
#   bash scripts/ci_tier1.sh
#
# Exit code is pytest's (pipefail-preserved through the tee) combined with
# the fused-edge regression gate below; the final DOTS_PASSED=N line is
# the per-run passed-test count the PROGRESS trajectory tracks. Change the
# pytest line ONLY together with ROADMAP.md.
cd "$(dirname "$0")/.." || exit 1
t1_start=$(date +%s)
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 1500 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; t1_dots=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); echo DOTS_PASSED=$t1_dots

# ---- suite trajectory (ISSUE 13): the suite's own duration + DOTS_PASSED
# become one kind=suite row in the cross-run perf ledger, and the sentinel
# turns the ROADMAP's hand-written "watch the margin" note into a machine
# check (warns when suite time exceeds 80% of the 1500s timeout; the
# duration regression gate stays advisory — the rig's noise history sets
# its tolerance, so it sharpens as the ledger grows). t1_dots is the ONE
# DOTS_PASSED computation — the printed line and the ledger row can
# never diverge.
t1_dur=$(( $(date +%s) - t1_start ))
t1_ledger="${NTS_LEDGER_DIR:-$PWD/docs/perf_runs/ledger}"
JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.perf_sentinel \
  record-suite --ledger "$t1_ledger" --duration "$t1_dur" \
  --dots "$t1_dots" --rc "$rc" --timeout 1500 \
|| echo "suite ledger row append failed (advisory)"
JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.perf_sentinel \
  check --ledger "$t1_ledger" --kind suite --suite-budget 1500
echo "SUITE_SENTINEL=rc$? (advisory; warns over 80% of the 1500s timeout)"

# ---- fused-edge regression gates (ISSUE 6) ---------------------------------
# (1) STRUCTURAL (hard): run the fused smoke cfg and diff its obs stream
# against an expected-zero baseline generated through the live obs
# registry (always schema-current). The only shared metric is
# edge_hbm_bytes_per_epoch, which is exactly 0 on the fused path — a
# future PR that silently reroutes KERNEL:fused_edge back to the eager
# edge chain makes it >0 and trips the zero-baseline absolute floor.
fused_rc=0
rm -rf /tmp/_t1_fused_base /tmp/_t1_fused_run
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_fused_base python - <<'EOF'
from neutronstarlite_tpu import obs
m = obs.open_run("FUSED_EDGE_BASELINE")
m.gauge_set("kernel.edge_hbm_bytes_per_epoch", 0)
m.run_summary(
    epochs=0, phases={}, memory={"available": False},
    epoch_time={"first_s": None, "warm_median_s": None,
                "compile_overhead_s": None},
)
m.close()
EOF
then
  JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_fused_run timeout -k 10 300 \
    python -m neutronstarlite_tpu.run configs/gat_cora_fused_smoke.cfg \
    > /tmp/_t1_fused_run.log 2>&1 \
  && JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.metrics_report \
    --diff /tmp/_t1_fused_base /tmp/_t1_fused_run --tol 0.05 \
  || fused_rc=$?
else
  fused_rc=$?
fi
if [ "$fused_rc" -ne 0 ]; then
  echo "FUSED_EDGE_GATE=FAIL (rc=$fused_rc)"
else
  echo "FUSED_EDGE_GATE=OK"
fi

# (2) TIMING (advisory on the CPU rig): the micro_bench edge-family leg,
# eager vs fused fwd+bwd at tiny scale, fed to the same --diff (each side
# one family; _eager/_fused suffixes canonicalize to shared keys). CPU
# timings of tiny shapes are noisy, so this leg reports and only fails
# the build when NTS_CI_MICRO_FATAL=1 (on-chip rigs flip it on).
micro_rc=0
JAX_PLATFORMS=cpu timeout -k 10 300 python -m neutronstarlite_tpu.tools.micro_bench \
  --scale 0.005 --iters 3 --ops edge_gat_eager,edge_ggcn_eager \
  > /tmp/_t1_micro_eager.json 2>/dev/null \
&& JAX_PLATFORMS=cpu timeout -k 10 300 python -m neutronstarlite_tpu.tools.micro_bench \
  --scale 0.005 --iters 3 --ops edge_gat_fused,edge_ggcn_fused \
  > /tmp/_t1_micro_fused.json 2>/dev/null \
&& JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.metrics_report \
  --diff /tmp/_t1_micro_eager.json /tmp/_t1_micro_fused.json --tol 1.0 \
|| micro_rc=$?
echo "FUSED_EDGE_MICRO_GATE=rc$micro_rc (advisory unless NTS_CI_MICRO_FATAL=1)"
if [ "${NTS_CI_MICRO_FATAL:-0}" = "1" ] && [ "$micro_rc" -ne 0 ]; then
  fused_rc=$micro_rc
fi

# ---- sampling-pipeline gates (ISSUE 7) -------------------------------------
# (1) STRUCTURAL (hard): run the pipeline smoke cfg twice — synchronous
# (NTS_SAMPLE_PIPELINE=sync overriding the cfg) and pipelined (as written)
# — and require (a) BITWISE loss parity between the two runs and (b) the
# pipelined stream to actually carry the pipeline telemetry
# (sample.stall_ms counter + sample_produce spans). NTS_NO_NATIVE=1 pins
# the graph build deterministic across the two processes (the native
# OpenMP builder orders tie edges nondeterministically per build), and
# NTS_SAMPLE_WORKERS=0 keeps the single-core CI rig from forking a pool.
samp_rc=0
rm -rf /tmp/_t1_samp_sync /tmp/_t1_samp_pipe
if JAX_PLATFORMS=cpu NTS_NO_NATIVE=1 NTS_SAMPLE_WORKERS=0 \
    NTS_METRICS_DIR=/tmp/_t1_samp_sync NTS_SAMPLE_PIPELINE=sync \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_sample_pipeline_smoke.cfg > /tmp/_t1_samp_sync.log 2>&1 \
  && JAX_PLATFORMS=cpu NTS_NO_NATIVE=1 NTS_SAMPLE_WORKERS=0 \
    NTS_METRICS_DIR=/tmp/_t1_samp_pipe \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_sample_pipeline_smoke.cfg > /tmp/_t1_samp_pipe.log 2>&1
then
  JAX_PLATFORMS=cpu python - <<'EOF' || samp_rc=$?
import glob, json, sys

def load(d):
    summary, events = None, []
    for p in sorted(glob.glob(d + "/*.jsonl")):
        for line in open(p, encoding="utf-8"):
            line = line.strip()
            if not line:
                continue
            e = json.loads(line)
            events.append(e)
            if e["event"] == "run_summary":
                summary = e
    return summary, events

sync, _ = load("/tmp/_t1_samp_sync")
pipe, pipe_events = load("/tmp/_t1_samp_pipe")
assert sync and pipe, "missing run_summary on a gate side"
assert sync["loss_history"] == pipe["loss_history"], (
    "sync vs pipelined loss history diverged:\n"
    f"  sync {sync['loss_history']}\n  pipe {pipe['loss_history']}"
)
counters = pipe.get("counters") or {}
assert "sample.stall_ms" in counters, "pipelined run carries no sample.stall_ms"
names = {e.get("name") for e in pipe_events if e["event"] == "span"}
assert "sample_produce" in names, f"no sample_produce spans (got {sorted(names)})"
assert "h2d_copy" in names, "no h2d_copy spans"
print(
    "sample gate: loss parity OK; stall "
    f"{counters['sample.stall_ms']:.1f} ms over "
    f"{int(counters.get('sample.produced', 0))} batches"
)
EOF
else
  samp_rc=$?
fi
if [ "$samp_rc" -ne 0 ]; then
  echo "SAMPLE_PIPELINE_GATE=FAIL (rc=$samp_rc)"
else
  echo "SAMPLE_PIPELINE_GATE=OK"
fi

# (2) TIMING (advisory on the CPU rig): the same two obs streams through
# metrics_report --diff (warm epoch time; sample_stall_ms is absent on the
# sync side so only the shared timing metrics gate). A single-core rig
# cannot overlap a producer thread with device compute, so this leg only
# fails the build when NTS_CI_MICRO_FATAL=1 (on-chip rigs flip it on).
samp_micro_rc=0
JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.metrics_report \
  --diff /tmp/_t1_samp_sync /tmp/_t1_samp_pipe --tol 1.0 \
|| samp_micro_rc=$?
echo "SAMPLE_PIPELINE_TIMING_GATE=rc$samp_micro_rc (advisory unless NTS_CI_MICRO_FATAL=1)"
if [ "${NTS_CI_MICRO_FATAL:-0}" = "1" ] && [ "$samp_micro_rc" -ne 0 ]; then
  samp_rc=$samp_micro_rc
fi

# ---- zero-H2D fused-epoch gates (ISSUE 19) ---------------------------------
# (1) STRUCTURAL (hard): run the fused smoke cfg (whole epoch as ONE
# on-device lax.scan dispatch over the resident CSR + feature slab —
# sample/fused.py) plus its sync twin (NTS_SAMPLE_PIPELINE=sync
# overriding the cfg) and require (a) sample.h2d_bytes EXACTLY 0 on the
# fused side while the sync side prices a nonzero per-batch payload
# (proof the counter is live, not just absent), (b) sample.dispatches ==
# EPOCHS (one scan dispatch per epoch), (c) exactly ONE epoch-program
# compile (zero steady-state recompiles), (d) a typed epoch_scan record
# per epoch with its own dispatches/h2d_bytes pins, and (e) loss-history
# DISTRIBUTION parity against the sync oracle — fused draws the same
# neighbor distribution through a different (on-device) stream, so the
# pin is per-epoch proximity, not bitwise equality (measured divergence
# on this fixture is ~0.005; the 0.05 gate is 10x that).
zeroh2d_rc=0
z2d_ledger="${NTS_LEDGER_DIR:-$PWD/docs/perf_runs/ledger}"
rm -rf /tmp/_t1_z2d_fused /tmp/_t1_z2d_sync
if JAX_PLATFORMS=cpu NTS_NO_NATIVE=1 NTS_SAMPLE_WORKERS=0 \
    NTS_METRICS_DIR=/tmp/_t1_z2d_fused NTS_LEDGER_DIR="$z2d_ledger" \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_sample_fused_smoke.cfg > /tmp/_t1_z2d_fused.log 2>&1 \
  && JAX_PLATFORMS=cpu NTS_NO_NATIVE=1 NTS_SAMPLE_WORKERS=0 \
    NTS_METRICS_DIR=/tmp/_t1_z2d_sync NTS_LEDGER_DIR="$z2d_ledger" \
    NTS_SAMPLE_PIPELINE=sync \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_sample_fused_smoke.cfg > /tmp/_t1_z2d_sync.log 2>&1
then
  JAX_PLATFORMS=cpu python - <<'EOF' || zeroh2d_rc=$?
import glob, json

def load(d):
    summary, events = None, []
    for p in sorted(glob.glob(d + "/*.jsonl")):
        for line in open(p, encoding="utf-8"):
            line = line.strip()
            if not line:
                continue
            e = json.loads(line)
            events.append(e)
            if e["event"] == "run_summary":
                summary = e
    return summary, events

fused, fused_events = load("/tmp/_t1_z2d_fused")
sync, _ = load("/tmp/_t1_z2d_sync")
assert fused and sync, "missing run_summary on a gate side"
fc = fused.get("counters") or {}
sc = sync.get("counters") or {}
epochs = int(fused.get("epochs") or 0)
assert epochs > 0, "fused run reports no epochs"
# (a) the zero-H2D pin — and the sync twin proves the counter is live
assert fc.get("sample.h2d_bytes") == 0, (
    f"fused run transferred {fc.get('sample.h2d_bytes')!r} H2D bytes "
    "(the whole point of the fused scan is exactly 0)"
)
assert (sc.get("sample.h2d_bytes") or 0) > 0, (
    "sync twin priced no H2D bytes — the counter is dead, so the fused "
    "0 above proves nothing"
)
# (b) one scan dispatch per epoch
assert fc.get("sample.dispatches") == epochs, (
    f"fused dispatches {fc.get('sample.dispatches')!r} != epochs {epochs}"
)
# (c) exactly one epoch-program compile across the run
compiles = {k: v for k, v in fc.items()
            if k.startswith("sample.epoch_compiles.")}
assert compiles and sum(compiles.values()) == 1, (
    f"expected exactly one epoch-scan compile, got {compiles}"
)
# (d) a typed epoch_scan record per epoch, each carrying its own pins
scans = [e for e in fused_events if e["event"] == "epoch_scan"]
assert len(scans) == epochs, (
    f"{len(scans)} epoch_scan records for {epochs} epochs"
)
for e in scans:
    assert e["dispatches"] == 1 and e["h2d_bytes"] == 0, e
# (e) distribution parity vs the sync oracle
fl, sl = fused["loss_history"], sync["loss_history"]
assert len(fl) == len(sl) == epochs
worst = max(abs(a - b) for a, b in zip(fl, sl))
assert worst <= 0.05, (
    f"fused vs sync loss diverged by {worst:.4f} (> 0.05):\n"
    f"  fused {fl}\n  sync  {sl}"
)
print(
    f"zero-H2D gate: {epochs} epochs = {int(fc['sample.dispatches'])} "
    f"dispatches, h2d_bytes 0 (sync priced "
    f"{int(sc['sample.h2d_bytes'])}), 1 compile, loss maxdiff "
    f"{worst:.4f}"
)
EOF
else
  zeroh2d_rc=$?
fi

# (2) SERVE (hard): the fused serve fast path (serve/engine.py) — a
# cache-miss request's sample+execute is ONE dispatch per bucket. Train
# a tiny sampled model in-process, serve through the fused engine, and
# pin the dispatch-count gauges: serve.fused_dispatches.bucket_N counts
# every predict, compile_counts stays at one per bucket (the AOT ladder
# never recompiles steady-state), and a clone shares the ladder.
if [ "$zeroh2d_rc" -eq 0 ]; then
  JAX_PLATFORMS=cpu NTS_SAMPLE_WORKERS=0 NTS_FINAL_EVAL=0 \
  timeout -k 10 300 python - <<'EOF' > /tmp/_t1_z2d_serve.log 2>&1 || zeroh2d_rc=$?
import tempfile

import numpy as np

from neutronstarlite_tpu.models.gcn_sample import GCNSampleTrainer
from neutronstarlite_tpu.serve.batcher import ServeOptions
from neutronstarlite_tpu.serve.engine import InferenceEngine
from neutronstarlite_tpu.serve.server import InferenceServer
from neutronstarlite_tpu.utils.config import InputInfo
from tests.test_models import _planted_data

cfg = InputInfo()
cfg.algorithm = "GCNSAMPLESINGLE"
cfg.vertices = 300
cfg.layer_string = "16-24-4"
cfg.fanout_string = "3-3"
cfg.batch_size = 16
cfg.epochs = 2
cfg.learn_rate = 0.01
cfg.decay_epoch = -1
cfg.drop_rate = 0.0
cfg.checkpoint_dir = tempfile.mkdtemp()
src, dst, datum = _planted_data(v_num=300, seed=11)
tk = GCNSampleTrainer.from_arrays(cfg, src, dst, datum)
tk.run()

opts = ServeOptions(max_batch=8, max_wait_ms=1, sample_pipeline="fused")
eng = InferenceEngine(tk, cfg.checkpoint_dir, options=opts,
                      rng=np.random.default_rng(0))
assert eng.fused
out = eng.predict(np.array([1, 2, 3]))
assert out.shape == (3, 4) and np.isfinite(out).all()
for _ in range(4):
    eng.predict(np.array([4, 5, 6]))
assert eng.compile_counts == {4: 1}, eng.compile_counts
snap = eng.metrics.snapshot()["counters"]
assert snap.get("serve.fused_dispatches.bucket_4") == 5.0, snap
# the clone (replica path) shares the compiled ladder
clone = eng.clone(rng=np.random.default_rng(1))
clone.predict(np.array([7]))
assert eng.compile_counts == {4: 1, 1: 1}, eng.compile_counts
# the server flush path routes through the same one-dispatch engine
srv = InferenceServer(eng)
rows = srv.predict([42, 43])
assert rows.shape == (2, 4) and np.isfinite(rows).all()
srv.close()
assert eng.compile_counts in ({4: 1, 1: 1}, {4: 1, 1: 1, 2: 1}), \
    eng.compile_counts
snap = eng.metrics.snapshot()["counters"]
fd = {k: int(v) for k, v in snap.items()
      if k.startswith("serve.fused_dispatches.")}
print(f"zero-H2D serve gate: dispatches {fd}, compiles {eng.compile_counts}")
EOF
  [ "$zeroh2d_rc" -eq 0 ] && grep "zero-H2D serve gate:" /tmp/_t1_z2d_serve.log
fi
if [ "$zeroh2d_rc" -ne 0 ]; then
  echo "ZEROH2D_GATE=FAIL (rc=$zeroh2d_rc)"
else
  grep "zero-H2D gate:" /tmp/_t1_z2d_fused.log /tmp/_t1_z2d_sync.log 2>/dev/null
  echo "ZEROH2D_GATE=OK"
fi

# (3) TIMING (advisory on the CPU rig): sync vs fused through
# metrics_report --diff (the shared warm-epoch metrics; the fused side's
# sample_h2d_bytes_per_epoch drop renders as -100%), and the two
# kind=run ledger rows the runs appended trend-gate against their own
# per-cfg history via perf_sentinel as the ledger grows.
z2d_adv_rc=0
JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.metrics_report \
  --diff /tmp/_t1_z2d_sync /tmp/_t1_z2d_fused --tol 1.0 \
|| z2d_adv_rc=$?
if [ "$z2d_adv_rc" -eq 0 ]; then
  JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.perf_sentinel \
    check --ledger "$z2d_ledger" --kind run || z2d_adv_rc=$?
fi
echo "ZEROH2D_TIMING_GATE=rc$z2d_adv_rc (advisory unless NTS_CI_MICRO_FATAL=1)"
if [ "${NTS_CI_MICRO_FATAL:-0}" = "1" ] && [ "$z2d_adv_rc" -ne 0 ]; then
  zeroh2d_rc=$z2d_adv_rc
fi

# ---- elastic degraded-mode gate (ISSUE 9) ----------------------------------
# STRUCTURAL (hard): inject a rank loss into the 4-partition sim-ring
# elastic smoke cfg and require the supervisor to survive it: the run
# exits 0 (supervised replan, not a retry-exhausted death), the stream
# carries the rank_loss detection and a replan record with 4 -> 3
# partitions, and the dist.active_partitions gauge ends at 3.
elastic_rc=0
rm -rf /tmp/_t1_elastic /tmp/_t1_elastic_ck
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_elastic NTS_ELASTIC=1 \
    NTS_HEARTBEAT_MISS_K=1 NTS_BACKOFF_BASE_S=0 \
    NTS_FAULT_SPEC='rank_loss@partition=2,epoch=1' \
    timeout -k 10 600 python -m neutronstarlite_tpu.run \
    configs/gcn_dist_elastic_smoke.cfg > /tmp/_t1_elastic.log 2>&1
then
  JAX_PLATFORMS=cpu python - <<'EOF' || elastic_rc=$?
import glob, json

from neutronstarlite_tpu.obs import schema

events = []
for p in sorted(glob.glob("/tmp/_t1_elastic/*.jsonl")):
    for line in open(p, encoding="utf-8"):
        line = line.strip()
        if line:
            events.append(json.loads(line))
assert schema.validate_stream(events) == len(events)
losses = [e for e in events if e["event"] == "rank_loss"]
replans = [e for e in events if e["event"] == "replan"]
assert losses, "no rank_loss detection record in the stream"
assert replans, "no replan record in the stream"
r = replans[-1]
assert (r["from_partitions"], r["to_partitions"]) == (4, 3), r
summ = [e for e in events if e["event"] == "run_summary"][-1]
active = summ["gauges"].get("dist.active_partitions")
assert active == 3, f"dist.active_partitions={active!r}, want 3 after replan"
print(
    "elastic gate: replanned 4->3 (lost partition "
    f"{r.get('lost')}, {r.get('moved_vertices')} vertices re-owned); "
    "run completed on the degraded mesh"
)
EOF
else
  elastic_rc=$?
  tail -30 /tmp/_t1_elastic.log
fi
if [ "$elastic_rc" -ne 0 ]; then
  echo "ELASTIC_GATE=FAIL (rc=$elastic_rc)"
else
  echo "ELASTIC_GATE=OK"
fi

# ---- autotuner gate (ISSUE 10) ---------------------------------------------
# STRUCTURAL (hard): run the all-auto tune smoke cfg twice into one
# NTS_TUNE_DIR. Run 1 (NTS_TUNE=measure) must exit 0 with a schema-valid
# stream carrying exactly one tune_decision whose tuple is a member of
# the funnel-valid candidate space, plus >=1 measured tune_trial. Run 2
# (NTS_TUNE=cached) must exit 0 with ZERO tune_trial records (cache hit,
# no re-measuring) and the IDENTICAL decision.
tune_rc=0
rm -rf /tmp/_t1_tune_obs1 /tmp/_t1_tune_obs2 /tmp/_t1_tune_cache
if JAX_PLATFORMS=cpu NTS_DIST_SIMULATE=1 NTS_TUNE=measure \
    NTS_TUNE_DIR=/tmp/_t1_tune_cache NTS_METRICS_DIR=/tmp/_t1_tune_obs1 \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_dist_tune_smoke.cfg > /tmp/_t1_tune1.log 2>&1 \
  && JAX_PLATFORMS=cpu NTS_DIST_SIMULATE=1 NTS_TUNE=cached \
    NTS_TUNE_DIR=/tmp/_t1_tune_cache NTS_METRICS_DIR=/tmp/_t1_tune_obs2 \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_dist_tune_smoke.cfg > /tmp/_t1_tune2.log 2>&1
then
  JAX_PLATFORMS=cpu python - <<'EOF' || tune_rc=$?
import glob, json

from neutronstarlite_tpu.models import get_algorithm
from neutronstarlite_tpu.obs import schema
from neutronstarlite_tpu.tune import space
from neutronstarlite_tpu.utils.config import InputInfo

def load(d):
    evs = []
    for p in sorted(glob.glob(d + "/*.jsonl")):
        for line in open(p, encoding="utf-8"):
            line = line.strip()
            if line:
                evs.append(json.loads(line))
    assert schema.validate_stream(evs) == len(evs)
    return evs

run1 = load("/tmp/_t1_tune_obs1")
run2 = load("/tmp/_t1_tune_obs2")
d1 = [e for e in run1 if e["event"] == "tune_decision"]
assert len(d1) == 1, f"run 1: want exactly one tune_decision, got {len(d1)}"
assert d1[0]["source"] == "measured", d1[0]
t1 = [e for e in run1 if e["event"] == "tune_trial"]
assert any(t["seconds"] is not None for t in t1), "run 1 measured nothing"
# the decided tuple is a member of the funnel-valid candidate space
cfg = InputInfo.read_from_cfg_file("configs/gcn_dist_tune_smoke.cfg")
cls = get_algorithm(cfg.algorithm)
valid = {c.label() for c in space.enumerate_candidates(
    cls, cfg, cfg.partitions, simulate=True)}
assert d1[0]["candidate"] in valid, (d1[0]["candidate"], sorted(valid))
# run 2: cache hit — zero trials, identical decision
t2 = [e for e in run2 if e["event"] == "tune_trial"]
assert not t2, f"cached run re-measured: {len(t2)} tune_trial records"
d2 = [e for e in run2 if e["event"] == "tune_decision"]
assert len(d2) == 1 and d2[0]["source"] == "cached", d2
assert d2[0]["candidate"] == d1[0]["candidate"], (d1[0], d2[0])
print(
    f"tune gate: measured -> {d1[0]['candidate']} over {len(t1)} "
    f"trial(s); cached replay identical with zero trials"
)
EOF
else
  tune_rc=$?
  tail -30 /tmp/_t1_tune1.log /tmp/_t1_tune2.log 2>/dev/null
fi
if [ "$tune_rc" -ne 0 ]; then
  echo "TUNE_GATE=FAIL (rc=$tune_rc)"
else
  echo "TUNE_GATE=OK"
fi

# ---- 2D-mesh gate (ISSUE 12) -----------------------------------------------
# STRUCTURAL (hard): run configs/gcn_dist_mesh_smoke.cfg on its (2, 2)
# sim mesh — exit 0, schema-valid stream, mesh.shape gauge present, live
# wire counters equal to wire_accounting.predict_mesh's 2D pricing, and
# per-hop ring_step records carrying the feature-slab width. Then the
# tune leg: NTS_MESH=auto over one NTS_TUNE_DIR — run 1 (NTS_TUNE=
# measure) decides a mesh shape with >=1 measured trial; run 2
# (NTS_TUNE=cached) replays the IDENTICAL decision with zero trials.
mesh_rc=0
rm -rf /tmp/_t1_mesh_obs /tmp/_t1_mesh_obs2 /tmp/_t1_mesh_obs3 /tmp/_t1_mesh_cache
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_mesh_obs \
    timeout -k 10 600 python -m neutronstarlite_tpu.run \
    configs/gcn_dist_mesh_smoke.cfg > /tmp/_t1_mesh.log 2>&1
then
  JAX_PLATFORMS=cpu python - <<'EOF' || mesh_rc=$?
import glob, json, os

from neutronstarlite_tpu.graph.storage import build_graph, load_edges
from neutronstarlite_tpu.obs import schema
from neutronstarlite_tpu.tools.wire_accounting import predict_mesh

events = []
for p in sorted(glob.glob("/tmp/_t1_mesh_obs/*.jsonl")):
    for line in open(p, encoding="utf-8"):
        line = line.strip()
        if line:
            events.append(json.loads(line))
assert schema.validate_stream(events) == len(events)
summ = [e for e in events if e["event"] == "run_summary"][-1]
g_ = summ["gauges"]
assert g_.get("mesh.shape") == "2x2", f"mesh.shape={g_.get('mesh.shape')!r}"
assert (g_["mesh.pv"], g_["mesh.pf"]) == (2, 2)

src, dst = load_edges("tests/fixtures/cora/cora.2708.edge.self")
g = build_graph(src, dst, 2708, weight="gcn_norm")
# standard order ships each layer's INPUT width; the input width (1433)
# rides the ring once, in the input_aggregate phase, and sets the peak
widths = [16]
pred = predict_mesh(g, 2, 2, widths, itemsize=4)
once = predict_mesh(g, 2, 2, [1433], itemsize=4)
epochs = 2
# live wire counters == the 2D analytic pricing (single slab_width def)
assert summ["counters"]["wire.bytes_fwd"] == pred["bytes_per_epoch"] * epochs, (
    summ["counters"]["wire.bytes_fwd"], pred["bytes_per_epoch"], epochs)
assert g_["wire.peak_resident_rows"] == pred["peak_resident_rows"]
assert g_["wire.peak_resident_feature_bytes"] == once[
    "peak_resident_feature_bytes"]
assert g_["wire.bytes_input_aggregate"] == once["bytes_per_epoch"]
assert g_["mesh.slab_cols"] == sum(pred["slab_widths"])
hops = [e for e in events if e["event"] == "ring_step"]
assert hops and all(h.get("slab_cols") == sum(pred["slab_widths"])
                    for h in hops), "ring_step records missing slab_cols"
assert sum(h["bytes"] for h in hops) == pred["bytes_per_epoch"] * epochs
print(
    f"mesh gate: 2x2 sim mesh OK — wire {summ['counters']['wire.bytes_fwd']}"
    f" B == predict_mesh x{epochs}, slab_cols {g_['mesh.slab_cols']}, "
    f"peak resident {g_['wire.peak_resident_feature_bytes']} B"
)
EOF
else
  mesh_rc=$?
  tail -30 /tmp/_t1_mesh.log
fi
if [ "$mesh_rc" -eq 0 ]; then
  if JAX_PLATFORMS=cpu NTS_MESH=auto NTS_TUNE=measure \
      NTS_TUNE_DIR=/tmp/_t1_mesh_cache NTS_METRICS_DIR=/tmp/_t1_mesh_obs2 \
      timeout -k 10 600 python -m neutronstarlite_tpu.run \
      configs/gcn_dist_mesh_smoke.cfg > /tmp/_t1_mesh2.log 2>&1 \
    && JAX_PLATFORMS=cpu NTS_MESH=auto NTS_TUNE=cached \
      NTS_TUNE_DIR=/tmp/_t1_mesh_cache NTS_METRICS_DIR=/tmp/_t1_mesh_obs3 \
      timeout -k 10 600 python -m neutronstarlite_tpu.run \
      configs/gcn_dist_mesh_smoke.cfg > /tmp/_t1_mesh3.log 2>&1
  then
    JAX_PLATFORMS=cpu python - <<'EOF' || mesh_rc=$?
import glob, json

def load(d):
    evs = []
    for p in sorted(glob.glob(d + "/*.jsonl")):
        for line in open(p, encoding="utf-8"):
            line = line.strip()
            if line:
                evs.append(json.loads(line))
    return evs

run1 = load("/tmp/_t1_mesh_obs2")
run2 = load("/tmp/_t1_mesh_obs3")
d1 = [e for e in run1 if e["event"] == "tune_decision"]
assert len(d1) == 1 and d1[0]["source"] == "measured", d1
assert "mesh" in (d1[0].get("decision") or {}), d1[0]
t1 = [e for e in run1 if e["event"] == "tune_trial"]
assert any(t["seconds"] is not None for t in t1), "run 1 measured nothing"
t2 = [e for e in run2 if e["event"] == "tune_trial"]
assert not t2, f"cached run re-measured: {len(t2)} tune_trial records"
d2 = [e for e in run2 if e["event"] == "tune_decision"]
assert len(d2) == 1 and d2[0]["source"] == "cached", d2
assert d2[0]["candidate"] == d1[0]["candidate"], (d1[0], d2[0])
print(
    f"mesh tune leg: measured -> {d1[0]['candidate']} "
    f"(mesh={d1[0]['decision'].get('mesh') or '1D'}) over {len(t1)} "
    "trial(s); cached replay identical with zero trials"
)
EOF
  else
    mesh_rc=$?
    tail -30 /tmp/_t1_mesh2.log /tmp/_t1_mesh3.log 2>/dev/null
  fi
fi
if [ "$mesh_rc" -ne 0 ]; then
  echo "MESH_GATE=FAIL (rc=$mesh_rc)"
else
  echo "MESH_GATE=OK"
fi

# ---- live telemetry gate (ISSUE 11) ----------------------------------------
# STRUCTURAL (hard): drive the serve smoke cfg with the exporter + SLO
# engine armed and inject a fault mid-serve. Requires: a live /metrics
# scrape that parses and carries the latency histogram, /healthz +
# /slo answering, a schema-valid stream with merged `hist` records and
# exactly one slo_status-emitting stream, and a schema-valid flight dump
# from the injected fault.
obs_rc=0
rm -rf /tmp/_t1_obs
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_obs NTS_METRICS_PORT=0 \
    NTS_SLO_SPEC='serve_p99_ms<=75@1m;shed_rate<=0.5@1m' \
    NTS_FLIGHT_DIR=/tmp/_t1_obs/flight NTS_SAMPLE_WORKERS=0 \
    timeout -k 10 600 python - <<'EOF' > /tmp/_t1_obs.log 2>&1
import glob, json, os, tempfile, urllib.request

import numpy as np

from neutronstarlite_tpu.serve.engine import InferenceEngine
from neutronstarlite_tpu.serve.server import InferenceServer
from neutronstarlite_tpu.tools.serve_bench import ensure_checkpoint
from neutronstarlite_tpu.utils.config import InputInfo

cfg_path = "configs/serve_cora_smoke.cfg"
cfg = InputInfo.read_from_cfg_file(cfg_path)
base_dir = os.path.dirname(os.path.abspath(cfg_path))
ckpt = tempfile.mkdtemp(prefix="obs_gate_ckpt_")
cfg.checkpoint_dir = ckpt
ensure_checkpoint(cfg, base_dir, ckpt, train=True)
engine = InferenceEngine.from_config(
    cfg, base_dir=base_dir, ckpt_dir=ckpt, rng=np.random.default_rng(0)
)
engine.warmup()
server = InferenceServer(engine)
assert server.exporter is not None, "exporter did not start"
assert server.slo is not None, "SLO engine did not arm"
v = engine.toolkit.host_graph.v_num
rng = np.random.default_rng(1)
for _ in range(30):
    try:
        server.predict(rng.integers(0, v, 1), timeout=60.0)
    except Exception:
        pass  # burn-rate sheds are an allowed outcome under the tight SLO
# live scrape MID-RUN (the non-blocking snapshot contract)
port = server.exporter.port
def get(path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.read().decode()
txt = get("/metrics")
assert "nts_serve_latency_ms_bucket" in txt, "no latency histogram in /metrics"
for line in txt.splitlines():
    if not line.startswith("#"):
        float(line.rsplit(" ", 1)[1])  # every sample parses
hz = json.loads(get("/healthz"))
assert hz["ok"] is True, hz
slo = json.loads(get("/slo"))
assert slo and slo[0]["objective"].startswith("serve_p99_ms"), slo
# injected fault -> flight dump off the live ring
from neutronstarlite_tpu.resilience import events

events.emit_fault("nonfinite_loss", epoch=1, injected=True)
server.close()

from neutronstarlite_tpu.obs import schema
from neutronstarlite_tpu.obs.hist import latest_hists

evs = []
for p in sorted(glob.glob("/tmp/_t1_obs/*.jsonl")):
    for line in open(p, encoding="utf-8"):
        line = line.strip()
        if line:
            evs.append(json.loads(line))
assert schema.validate_stream(evs) == len(evs)
hists = latest_hists(evs)
assert hists.get("serve.latency_ms") is not None, "no hist records"
assert hists["serve.latency_ms"].count > 0
slos = [e for e in evs if e["event"] == "slo_status"]
assert slos, "no slo_status records in the stream"
slo_streams = {e["run_id"] for e in slos}
assert len(slo_streams) == 1, f"slo_status from {len(slo_streams)} streams"
dumps = sorted(glob.glob("/tmp/_t1_obs/flight/flight_*.jsonl"))
assert dumps, "injected fault left no flight dump"
drecs = [json.loads(l) for l in open(dumps[-1], encoding="utf-8")
         if l.strip()]
assert schema.validate_stream(drecs) == len(drecs)
assert any(e["event"] == "fault" for e in drecs), "fault not in the dump"
print(
    f"obs gate: /metrics histogram OK ({hists['serve.latency_ms'].count} "
    f"samples); {len(slos)} slo_status record(s) from one stream; flight "
    f"dump carries {len(drecs)} schema-valid records"
)
EOF
then
  grep "obs gate:" /tmp/_t1_obs.log
else
  obs_rc=$?
  tail -30 /tmp/_t1_obs.log
fi
if [ "$obs_rc" -ne 0 ]; then
  echo "OBS_GATE=FAIL (rc=$obs_rc)"
else
  echo "OBS_GATE=OK"
fi

# ---- perf ledger + sentinel gate (ISSUE 13) --------------------------------
# STRUCTURAL (hard): run the gcn_cora smoke TWICE into one fresh
# NTS_LEDGER_DIR. Requires: two kind=run ledger rows with MATCHING keys
# (graph digest + cfg fingerprint + backend), each carrying the captured
# program_cost records; the sentinel exits 0 against its own (thin)
# history; then a synthetically corrupted third row (warm epoch x10)
# makes the sentinel exit 2 — the exit-2 contract, proven end to end.
ledger_rc=0
rm -rf /tmp/_t1_ledger /tmp/_t1_ledger_obs1 /tmp/_t1_ledger_obs2
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_ledger_obs1 \
    NTS_LEDGER_DIR=/tmp/_t1_ledger \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_cora_smoke.cfg > /tmp/_t1_ledger1.log 2>&1 \
  && JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_ledger_obs2 \
    NTS_LEDGER_DIR=/tmp/_t1_ledger \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_cora_smoke.cfg > /tmp/_t1_ledger2.log 2>&1
then
  JAX_PLATFORMS=cpu python - <<'EOF' || ledger_rc=$?
import subprocess, sys

from neutronstarlite_tpu.obs import ledger

D = "/tmp/_t1_ledger"
rows = ledger.read_rows(directory=D)
runs = [r for r in rows if r["kind"] == "run"]
assert len(runs) == 2, f"want 2 run rows, got {len(runs)}"
k0, k1 = ledger.row_key(runs[0]), ledger.row_key(runs[1])
assert k0 == k1, f"ledger keys diverged between identical runs:\n  {k0}\n  {k1}"
assert runs[0]["graph_digest"] and runs[0]["cfg"], runs[0]
for r in runs:
    assert r.get("program_costs"), "run row carries no program_cost records"
    assert r.get("warm_median_epoch_s"), r

def sentinel(*args):
    return subprocess.run(
        [sys.executable, "-m", "neutronstarlite_tpu.tools.perf_sentinel",
         "check", "--ledger", D, *args],
        capture_output=True, text=True,
    )

r = sentinel()
assert r.returncode == 0, (
    f"sentinel rc={r.returncode} against its own history:\n{r.stdout}\n{r.stderr}"
)
# synthetically corrupted third row: 10x warm epoch, same key
bad = dict(runs[-1])
bad["warm_median_epoch_s"] = runs[-1]["warm_median_epoch_s"] * 10
bad["avg_epoch_s"] = (runs[-1].get("avg_epoch_s") or 0) * 10
ledger.append_row(bad, directory=D)
r = sentinel()
assert r.returncode == 2, (
    f"sentinel rc={r.returncode} on a 10x epoch-time row (want 2):\n"
    f"{r.stdout}\n{r.stderr}"
)
print(
    "ledger gate: 2 matching run rows (digest "
    f"{runs[0]['graph_digest'][:12]}, cfg {runs[0]['cfg'][:12]}), "
    f"{len(runs[0]['program_costs'])} program cost(s)/run; sentinel 0 on "
    "clean history, 2 on the corrupted row"
)
EOF
else
  ledger_rc=$?
  tail -30 /tmp/_t1_ledger1.log /tmp/_t1_ledger2.log 2>/dev/null
fi
if [ "$ledger_rc" -ne 0 ]; then
  echo "LEDGER_GATE=FAIL (rc=$ledger_rc)"
else
  echo "LEDGER_GATE=OK"
fi

# ---- serve-fleet gate (ISSUE 14) -------------------------------------------
# STRUCTURAL (hard): 3-replica fleet over the serve_fleet_smoke cfg.
# (1) inject a single-replica SLO breach -> every request routes AROUND
# it with ZERO fleet-level sheds; (2) kill a replica -> the heartbeat
# monitor detects it (rank_loss record), restarts it supervised
# (recovery action=restart) and serving continues -> exit 0; (3) apply a
# graph delta -> post-delta predictions match a FRESH engine built on
# the post-delta edge list bitwise, with only the touched embedding-
# cache entries invalidated. NTS_NO_NATIVE=1 pins the fresh-build edge
# order (the delta rebuild is numpy-canonical).
fleet_rc=0
rm -rf /tmp/_t1_fleet
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_fleet NTS_NO_NATIVE=1 \
    NTS_SAMPLE_WORKERS=0 NTS_SLO_SPEC='serve_p99_ms<=5000@30s' \
    NTS_SERVE_HEARTBEAT_S=0.1 NTS_HEARTBEAT_MISS_K=2 \
    timeout -k 10 600 python - <<'EOF' > /tmp/_t1_fleet.log 2>&1
import glob, json, os, tempfile, time

import numpy as np

from neutronstarlite_tpu.serve.delta import GraphDelta, plan_delta
from neutronstarlite_tpu.serve.engine import InferenceEngine
from neutronstarlite_tpu.serve.fleet import ReplicaSet
from neutronstarlite_tpu.tools.serve_bench import ensure_checkpoint
from neutronstarlite_tpu.utils.config import InputInfo

cfg_path = "configs/serve_fleet_smoke.cfg"
cfg = InputInfo.read_from_cfg_file(cfg_path)
base_dir = os.path.dirname(os.path.abspath(cfg_path))
ckpt = tempfile.mkdtemp(prefix="fleet_gate_ckpt_")
cfg.checkpoint_dir = ckpt
ensure_checkpoint(cfg, base_dir, ckpt, train=True)
engine = InferenceEngine.from_config(
    cfg, base_dir=base_dir, ckpt_dir=ckpt, rng=np.random.default_rng(0)
)
engine.warmup()
fleet = ReplicaSet.from_engine(engine, 3, seed=0)
assert len(fleet.replicas) == 3
v = engine.toolkit.host_graph.v_num
rng = np.random.default_rng(1)

# ---- leg 1: single-replica breach -> route around, zero fleet sheds
bad = fleet.replicas[1]
for _ in range(30):
    bad.server.metrics.hist_observe("serve.latency_ms", 1e6)
bad.server.slo.tick(force=True)
assert bad.route_state()["draining"] is True, "injected breach not seen"
reqs = [fleet.submit(rng.integers(0, v, 1)) for _ in range(30)]
for r in reqs:
    r.result(timeout=60.0)
assert fleet.shed_count == 0, f"fleet shed {fleet.shed_count} request(s)"
assert bad.server.request_count == 0, "requests routed INTO the breach"

# ---- leg 2: replica kill -> supervised restart, serving continues
victim = fleet.replicas[0]
fleet.inject_replica_death(0)
deadline = time.time() + 20.0
while time.time() < deadline:
    if fleet.replicas[0] is not victim and fleet.replicas[0].beating():
        break
    time.sleep(0.1)
assert fleet.replicas[0] is not victim, "dead replica never restarted"
assert fleet.replicas[0].restarts == 1
reqs = [fleet.submit(rng.integers(0, v, 1)) for _ in range(10)]
for r in reqs:
    r.result(timeout=60.0)
assert fleet.shed_count == 0

# ---- leg 3: graph delta -> fresh-engine oracle + incremental cache
g = engine.sampler.graph
u, d0 = int(g.row_indices[0]), int(g.dst_of_edge[0])
delta = GraphDelta.edges(
    add=[(5, 17), (1200, 17), (17, 421)], remove=[(u, d0)]
)
preview = plan_delta(g, delta, hops=len(engine.fanouts))
clean_vid = next(i for i in range(v) if i not in set(preview.dirty.tolist()))
dirty_vid = int(preview.dirty[0])
r0 = fleet.replicas[0].server
r0.predict([dirty_vid], timeout=60.0)
r0.predict([clean_vid], timeout=60.0)
assert r0.cache.lookup(dirty_vid) is not None
plan = fleet.apply_delta(delta)
assert r0.cache.lookup(dirty_vid) is None, "dirty entry survived the delta"
assert r0.cache.lookup(clean_vid) is not None, "clean entry was invalidated"

edge_file = tempfile.mktemp(suffix=".edge.txt")
with open(edge_file, "w") as fh:
    for s_, t_ in zip(plan.src.tolist(), plan.dst.tolist()):
        fh.write(f"{s_} {t_}\n")
cfg2 = InputInfo.read_from_cfg_file(cfg_path)
cfg2.edge_file = edge_file
cfg2.checkpoint_dir = ckpt
fresh = InferenceEngine.from_config(
    cfg2, base_dir=base_dir, ckpt_dir=ckpt, rng=np.random.default_rng(777)
)
probe = engine.clone(rng=np.random.default_rng(777))
for _ in range(4):
    seeds = rng.integers(0, v, size=int(rng.integers(1, 8)))
    a, b = probe.predict(seeds), fresh.predict(seeds)
    assert np.array_equal(a, b), f"delta oracle diverged on {seeds}"

stats = fleet.close()
assert stats["fleet_shed"] == 0 and stats["restarts"] == 1

from neutronstarlite_tpu.obs import schema

evs = []
for p in sorted(glob.glob("/tmp/_t1_fleet/*.jsonl")):
    for line in open(p, encoding="utf-8"):
        if line.strip():
            evs.append(json.loads(line))
assert schema.validate_stream(evs) == len(evs)
kinds = {e["event"] for e in evs}
assert "rank_loss" in kinds, "kill left no rank_loss record"
assert any(e["event"] == "recovery" and e.get("action") == "restart"
           for e in evs), "no supervised-restart recovery record"
deltas = [e for e in evs if e["event"] == "graph_delta"]
assert len(deltas) == 3, f"want one graph_delta per replica, got {len(deltas)}"
assert all(e["graph_digest"] == plan.digest for e in deltas)
print(
    f"fleet gate: routed around the breach (30 req, 0 fleet sheds, "
    f"breaching replica served 0); kill -> restart #1 -> 10 more served; "
    f"delta oracle bitwise over 4 batches, cache kept {clean_vid} "
    f"dropped {dirty_vid}; digest {plan.digest[:12]}"
)
EOF
then
  grep "fleet gate:" /tmp/_t1_fleet.log
else
  fleet_rc=$?
  tail -30 /tmp/_t1_fleet.log
fi
if [ "$fleet_rc" -ne 0 ]; then
  echo "FLEET_GATE=FAIL (rc=$fleet_rc)"
else
  echo "FLEET_GATE=OK"
fi

# TIMING (advisory on the CPU rig): continuous batching vs single-flush
# on the same open-loop load, both rows into the perf ledger (kind=serve,
# keyed by load shape) so the sentinel trend-gates serve p99 across runs;
# the pairwise CB-vs-sync comparison prints here and only fails the build
# when NTS_CI_MICRO_FATAL=1 (a 1-core rig cannot overlap produce with
# execute, so wall-clock wins are not guaranteed there).
if [ "$fleet_rc" -eq 0 ]; then
  fleet_ckpt=$(ls -dt /tmp/fleet_gate_ckpt_* 2>/dev/null | head -1)
  cb_rc=0
  JAX_PLATFORMS=cpu NTS_SAMPLE_WORKERS=0 NTS_NO_NATIVE=1 \
    NTS_LEDGER_DIR="$t1_ledger" NTS_METRICS_DIR=/tmp/_t1_fleet_cb0 \
    timeout -k 10 300 python -m neutronstarlite_tpu.tools.serve_bench \
    configs/serve_fleet_smoke.cfg "$fleet_ckpt" --mode open --rps 150 \
    --requests 120 --replicas 1 --cb 0 > /tmp/_t1_cb0.json 2>/dev/null \
  && JAX_PLATFORMS=cpu NTS_SAMPLE_WORKERS=0 NTS_NO_NATIVE=1 \
    NTS_LEDGER_DIR="$t1_ledger" NTS_METRICS_DIR=/tmp/_t1_fleet_cb1 \
    timeout -k 10 300 python -m neutronstarlite_tpu.tools.serve_bench \
    configs/serve_fleet_smoke.cfg "$fleet_ckpt" --mode open --rps 150 \
    --requests 120 --replicas 1 --cb 1 > /tmp/_t1_cb1.json 2>/dev/null \
  && python - <<'EOF' || cb_rc=$?
import json

def p99(path):
    for line in open(path):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)["extra"]["p99_ms"]
    raise SystemExit(f"no JSON line in {path}")

a, b = p99("/tmp/_t1_cb0.json"), p99("/tmp/_t1_cb1.json")
print(f"continuous batching leg: p99 sync={a:.2f}ms cb={b:.2f}ms "
      f"({(b - a) / a * 100:+.1f}%)")
raise SystemExit(0 if b <= a * 1.05 else 2)
EOF
  echo "FLEET_CB_GATE=rc$cb_rc (advisory unless NTS_CI_MICRO_FATAL=1)"
  if [ "${NTS_CI_MICRO_FATAL:-0}" = "1" ] && [ "$cb_rc" -ne 0 ]; then
    fleet_rc=$cb_rc
  fi
fi

# ---- numerics health-plane gate (ISSUE 15) ---------------------------------
# STRUCTURAL (hard), two legs:
# (1) the chaos oracle — the fullbatch smoke under supervision with
#     nan_loss@epoch=1,layer=1 and NTS_NUMERICS=1 must exit 0 (supervised
#     recovery), leaving a schema-valid stream that carries tensor_stats
#     records AND a nonfinite_provenance record naming layer 1 exactly;
# (2) the quant leg — the bf16 sim-ring smoke with NTS_QUANT_PROBE=1 must
#     leave the wire.quant_rel_err gauge + per-epoch wire.payload/l0
#     records (the measurement tools/drift_audit audits vs NTS_QUANT_TOL).
numerics_rc=0
rm -rf /tmp/_t1_num_prov /tmp/_t1_num_quant /tmp/_t1_num_off /tmp/_t1_num_on
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_num_prov NTS_NUMERICS=1 \
    NTS_FAULT_SPEC='nan_loss@epoch=1,layer=1' NTS_MAX_RESTARTS=2 \
    NTS_BACKOFF_BASE_S=0 timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_cora_smoke.cfg > /tmp/_t1_num_prov.log 2>&1 \
  && JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_num_quant NTS_NUMERICS=1 \
    NTS_QUANT_PROBE=1 NTS_WIRE_DTYPE=bf16 NTS_DIST_SIMULATE=1 \
    NTS_LEDGER_DIR="$t1_ledger" timeout -k 10 300 \
    python -m neutronstarlite_tpu.run \
    configs/gcn_dist_ring_smoke.cfg > /tmp/_t1_num_quant.log 2>&1
then
  JAX_PLATFORMS=cpu python - <<'EOF' || numerics_rc=$?
import glob, json

from neutronstarlite_tpu.obs import schema

def load(d):
    evs = []
    for p in sorted(glob.glob(d + "/*.jsonl")):
        for line in open(p, encoding="utf-8"):
            if line.strip():
                evs.append(json.loads(line))
    assert schema.validate_stream(evs) == len(evs)
    return evs

# leg 1: recovered chaos run with provenance naming layer 1
evs = load("/tmp/_t1_num_prov")
stats = [e for e in evs if e["event"] == "tensor_stats"]
assert stats, "no tensor_stats records in the numerics smoke stream"
prov = [e for e in evs if e["event"] == "nonfinite_provenance"]
assert prov, "no nonfinite_provenance record after the injected nan_loss"
assert prov[-1]["layer"] == 1, f"provenance named {prov[-1]['layer']}, want 1"
assert prov[-1]["injected"] is True

# leg 2: measured wire quant error on the bf16 ring
evs = load("/tmp/_t1_num_quant")
payloads = [e for e in evs if e["event"] == "tensor_stats"
            and e["name"] == "wire.payload/l0"]
assert payloads, "no wire.payload/l0 probe records on the bf16 ring smoke"
summ = [e for e in evs if e["event"] == "run_summary"][-1]
err = summ["gauges"].get("wire.quant_rel_err")
assert err is not None and 0 < err < 0.01, f"wire.quant_rel_err={err!r}"
print(
    f"numerics gate: provenance named layer {prov[-1]['layer']} "
    f"(op={prov[-1]['op']}), {len(stats)} tensor_stats records; "
    f"bf16 ring quant_rel_err={err:.2e} over {len(payloads)} epochs"
)
EOF
else
  numerics_rc=$?
  tail -30 /tmp/_t1_num_prov.log /tmp/_t1_num_quant.log
fi
if [ "$numerics_rc" -ne 0 ]; then
  echo "NUMERICS_GATE=FAIL (rc=$numerics_rc)"
else
  echo "NUMERICS_GATE=OK"
fi

# TIMING (advisory on the CPU rig): the overhead pin's wall-clock half —
# the same smoke with stats off vs fused-stats on through --diff; the
# jaxpr byte-identity half is a tier-1 test (tests/test_numerics.py).
# Plus the grad-norm sentinel leg: the quant run's kind=run ledger row
# carries grad_global_norm, and perf_sentinel's two-sided advisory check
# warns when it drifts off its own history (never gates).
if [ "$numerics_rc" -eq 0 ]; then
  num_t_rc=0
  JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_num_off timeout -k 10 300 \
    python -m neutronstarlite_tpu.run configs/gcn_cora_smoke.cfg \
    > /tmp/_t1_num_off.log 2>&1 \
  && JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_num_on NTS_NUMERICS=1 \
    timeout -k 10 300 python -m neutronstarlite_tpu.run \
    configs/gcn_cora_smoke.cfg > /tmp/_t1_num_on.log 2>&1 \
  && JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.metrics_report \
    --diff /tmp/_t1_num_off /tmp/_t1_num_on --tol 1.0 \
  || num_t_rc=$?
  echo "NUMERICS_TIMING_GATE=rc$num_t_rc (advisory unless NTS_CI_MICRO_FATAL=1)"
  if [ "${NTS_CI_MICRO_FATAL:-0}" = "1" ] && [ "$num_t_rc" -ne 0 ]; then
    numerics_rc=$num_t_rc
  fi
  JAX_PLATFORMS=cpu python -m neutronstarlite_tpu.tools.perf_sentinel \
    check --ledger "$t1_ledger" --kind run || true
  echo "NUMERICS_GRAD_SENTINEL=advisory (two-sided grad_global_norm warning only)"
fi

# ---- fleet telemetry hub gate (ISSUE 16) -----------------------------------
# STRUCTURAL (hard): 3 exporter-armed smoke processes serve /telemetry
# over real sockets; the hub polls them and must (a) merge the fleet p99
# to within the documented histogram bound (~1% bucket error, asserted
# at 2.1% — two half-bucket roundings) of the client-side exact sort,
# (b) survive a SIGKILL'd target as ONE schema-valid target_loss record
# with its own /healthz DEGRADED but alive, and (c) hand the merged
# stream to tools/dashboard.py for an exit-0 HTML render.
hub_rc=0
rm -rf /tmp/_t1_hub
mkdir -p /tmp/_t1_hub
if JAX_PLATFORMS=cpu timeout -k 10 300 python - > /tmp/_t1_hub.log 2>&1 <<'EOF'
import json, math, os, signal, subprocess, sys, time
import urllib.request

HUB = "/tmp/_t1_hub"
PY = sys.executable
child_src = r'''
import os, sys, time
from neutronstarlite_tpu.obs import registry
from neutronstarlite_tpu.obs.exporter import MetricsExporter

idx = int(sys.argv[1])
reg = registry.MetricsRegistry(f"serve-r{idx}-{os.getpid()}",
                               algorithm="SERVE", fingerprint="f")
vals = {0: [float(v) for v in range(1, 101)],
        1: [10.0 + 0.5 * i for i in range(200)],
        2: [250.0] * 20 + [5.0] * 80}[idx]
for v in vals:
    reg.hist_observe("serve.latency_ms", v)
exp = MetricsExporter(reg, port=0)
with open(f"/tmp/_t1_hub/port{idx}.tmp", "w") as fh:
    fh.write(str(exp.port))
os.replace(f"/tmp/_t1_hub/port{idx}.tmp", f"/tmp/_t1_hub/port{idx}")
time.sleep(300)
'''
procs = [subprocess.Popen([PY, "-c", child_src, str(i)]) for i in range(3)]
try:
    ports = []
    deadline = time.time() + 60
    for i in range(3):
        path = f"{HUB}/port{i}"
        while not os.path.exists(path):
            assert time.time() < deadline, f"target {i} never came up"
            time.sleep(0.1)
        ports.append(int(open(path).read()))

    os.environ["NTS_METRICS_DIR"] = f"{HUB}/obs"
    from neutronstarlite_tpu.obs import schema
    from neutronstarlite_tpu.obs.exporter import MetricsExporter
    from neutronstarlite_tpu.obs.hub import TelemetryHub

    hub = TelemetryHub([f"127.0.0.1:{p}" for p in ports], poll_s=0.2,
                       miss_k=2, ledger_dir=f"{HUB}/ledger")
    hub_exp = MetricsExporter(hub.registry, port=0)
    s = hub.poll_once()
    assert s["targets_ok"] == 3, s

    all_vals = ([float(v) for v in range(1, 101)]
                + [10.0 + 0.5 * i for i in range(200)]
                + [250.0] * 20 + [5.0] * 80)
    sv = sorted(all_vals)
    exact = sv[min(len(sv) - 1, math.ceil(0.99 * len(sv)) - 1)]
    merged = hub.merged_hists()["serve.latency_ms"]
    assert merged.count == len(all_vals), merged.count
    err = abs(merged.quantile(0.99) - exact) / exact
    assert err <= 0.021, (
        f"merged p99 {merged.quantile(0.99):.2f} vs exact {exact:.2f}: "
        f"{err:.4f} outside the documented bound"
    )

    def healthz():
        url = f"http://127.0.0.1:{hub_exp.port}/healthz"
        with urllib.request.urlopen(url, timeout=10) as r:
            return json.loads(r.read().decode())

    h = healthz()
    assert h["ok"] is True and h["hub"]["degraded"] is False, h

    procs[2].send_signal(signal.SIGKILL)
    procs[2].wait(timeout=30)
    for _ in range(3):
        s = hub.poll_once()
    assert s["targets_ok"] == 2 and s["targets_lost"] == 1, s
    h = healthz()
    assert h["ok"] is True, ("the hub must DEGRADE, not exit: %r" % h)
    assert h["hub"]["degraded"] is True and h["hub"]["targets_lost"] == 1, h
    # the lost target's snapshot stays frozen in the merge
    assert hub.merged_hists()["serve.latency_ms"].count == len(all_vals)
    stream = hub.stream_path()
    hub_exp.close()
    hub.close()

    events = [json.loads(l) for l in open(stream) if l.strip()]
    assert schema.validate_stream(events) == len(events)
    losses = [e for e in events if e["event"] == "target_loss"]
    assert len(losses) == 1 and losses[0]["reason"] == "poll_miss", losses

    r = subprocess.run([PY, "-m", "neutronstarlite_tpu.tools.dashboard",
                        "--stream", f"{HUB}/obs",
                        "--ledger", f"{HUB}/ledger",
                        "--out", f"{HUB}/fleet.html"])
    assert r.returncode == 0, "dashboard render failed"
    doc = open(f"{HUB}/fleet.html").read()
    assert "DEGRADED" in doc and "fleet topology" in doc

    print(
        f"hub gate: 3-target merge p99 within {err * 100:.2f}% of the "
        "exact sort; SIGKILL'd target -> 1 target_loss, hub "
        "degraded-but-alive; dashboard rendered"
    )
finally:
    for p in procs:
        if p.poll() is None:
            p.kill()
EOF
then
  :
else
  hub_rc=$?
  tail -40 /tmp/_t1_hub.log
fi
if [ "$hub_rc" -ne 0 ]; then
  echo "HUB_GATE=FAIL (rc=$hub_rc)"
else
  echo "HUB_GATE=OK"
fi

# ADVISORY straggler chaos leg: a 600 ms sleep injected into partition
# 2's step (slow_rank, 3 epochs) on the 4-partition elastic smoke cfg
# must surface as a typed straggler record naming partition 2 — and NO
# rank_loss (slow is advisory, dead is actionable; docs/RESILIENCE.md).
strag_rc=0
rm -rf /tmp/_t1_strag /tmp/_t1_elastic_ck
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_strag NTS_STRAGGLER=1 \
    NTS_STRAGGLER_M=2 \
    NTS_FAULT_SPEC='slow_rank@partition=2,ms=600,times=3' \
    timeout -k 10 600 python -m neutronstarlite_tpu.run \
    configs/gcn_dist_elastic_smoke.cfg > /tmp/_t1_strag.log 2>&1
then
  JAX_PLATFORMS=cpu python - <<'EOF' || strag_rc=$?
import glob, json

from neutronstarlite_tpu.obs import schema

events = []
for p in sorted(glob.glob("/tmp/_t1_strag/*.jsonl")):
    for line in open(p, encoding="utf-8"):
        line = line.strip()
        if line:
            events.append(json.loads(line))
assert schema.validate_stream(events) == len(events)
stragglers = [e for e in events if e["event"] == "straggler"]
assert stragglers, "no straggler record despite the injected slow_rank"
assert all(s["partition"] == 2 for s in stragglers), stragglers
assert not [e for e in events if e["event"] == "rank_loss"], (
    "a slow partition must NOT be reported dead"
)
s = stragglers[0]
print(
    f"straggler gate: partition 2 flagged at epoch {s['epoch']} "
    f"(+{s['excess'] * 100:.0f}% over the fleet median, "
    f"{s['consecutive']} consecutive); no rank_loss"
)
EOF
else
  strag_rc=$?
  tail -30 /tmp/_t1_strag.log
fi
echo "HUB_STRAGGLER_GATE=rc$strag_rc (advisory unless NTS_CI_MICRO_FATAL=1)"
if [ "${NTS_CI_MICRO_FATAL:-0}" = "1" ] && [ "$strag_rc" -ne 0 ]; then
  hub_rc=$strag_rc
fi

# ---- cross-host serve gate (ISSUE 17) --------------------------------------
# STRUCTURAL (hard): a 3-PROCESS fleet — router + spawned serve children
# over real sockets (serve/crosshost) — under open-loop load must
# (1) survive a SIGKILL'd replica: supervised respawn from the recorded
#     launch recipe (EXACTLY one typed target_loss + one recovery
#     action=restart) with ZERO fleet-level sheds — every owed request
#     re-routes to a survivor;
# (2) complete one rolling rollout under the same load: digest preflight
#     + canary gate -> 3 sequential drain/restarts -> exactly one typed
#     rollout record (verdict=promoted, canary attached) and kind=fleet
#     ledger rows whose merged p99, once established, never goes null
#     across the roll (the drain freeze keeps the merge continuous);
# (3) post-rollout, every replica answers a replay_seed /predict probe
#     BITWISE equal to a fresh single-process engine built from the
#     promoted checkpoint (the rng-neutral state-swap on both sides).
crosshost_rc=0
rm -rf /tmp/_t1_xh
mkdir -p /tmp/_t1_xh
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_xh/obs NTS_NO_NATIVE=1 \
    NTS_SAMPLE_WORKERS=0 NTS_SLO_SPEC='serve_p99_ms<=5000@30s' \
    timeout -k 10 900 python - > /tmp/_t1_xh.log 2>&1 <<'EOF'
import glob, json, os, shutil, signal, threading, time

import numpy as np

from neutronstarlite_tpu.obs import httpc, ledger, schema
from neutronstarlite_tpu.serve.crosshost import CrossHostFleet
from neutronstarlite_tpu.serve.engine import InferenceEngine
from neutronstarlite_tpu.tools.serve_bench import (
    ensure_checkpoint, run_open_loop,
)
from neutronstarlite_tpu.utils.config import InputInfo

XH = "/tmp/_t1_xh"
cfg_path = "configs/serve_fleet_smoke.cfg"
cfg = InputInfo.read_from_cfg_file(cfg_path)
base_dir = os.path.dirname(os.path.abspath(cfg_path))
ckpt1, ckpt2 = f"{XH}/ckpt_v1", f"{XH}/ckpt_v2"
cfg.checkpoint_dir = ckpt1
ensure_checkpoint(cfg, base_dir, ckpt1, train=True)
shutil.copytree(ckpt1, ckpt2)  # the candidate: byte-identical params

# the single-process oracle for leg 3, built on the candidate
oracle = InferenceEngine.from_config(
    cfg, base_dir=base_dir, ckpt_dir=ckpt2, rng=np.random.default_rng(0)
)
oracle.warmup()
v = oracle.toolkit.host_graph.v_num

fleet = CrossHostFleet.spawn(
    cfg_path, ckpt1, 3, spawn_dir=f"{XH}/spawn",
    poll_s=0.25, miss_k=2, ledger_dir=f"{XH}/ledger", ledger_every=1,
)
try:
    # ---- leg 1: SIGKILL one replica under open-loop load
    out = {}
    t = threading.Thread(target=lambda: out.update(
        e1=run_open_loop(fleet, v, 120, 60.0, 1, 7)))
    t.start()
    time.sleep(0.5)
    victim = fleet.replicas[1]
    victim.proc.send_signal(signal.SIGKILL)
    t.join(timeout=300.0)
    assert out.get("e1") == 0, f"leg1 dropped {out.get('e1')} request(s)"
    deadline = time.time() + 60.0
    while time.time() < deadline and (
        victim.restarts == 0 or fleet.hub.targets[1].lost
    ):
        time.sleep(0.2)
    assert victim.restarts == 1, "SIGKILL'd replica never respawned"
    assert not fleet.hub.targets[1].lost, "respawned replica never rejoined"

    # ---- leg 2: rolling rollout under load (the pump spans the WHOLE
    # roll, so the fresh children keep receiving observations and the
    # merged-p99 ledger trajectory stays continuous)
    stop, errs = threading.Event(), []
    def pump():
        while not stop.is_set():
            errs.append(run_open_loop(fleet, v, 60, 60.0, 1, 8))
    t2 = threading.Thread(target=pump)
    t2.start()
    time.sleep(0.5)
    rec = fleet.rollout(ckpt2)
    stop.set()
    t2.join(timeout=300.0)
    assert rec["verdict"] == "promoted", rec
    assert rec["restarted"] == 3 and rec["rolled_back"] == 0, rec
    assert rec["canary"] and rec["canary"]["passed"], rec
    assert rec["canary"]["disagreement"] == 0.0, rec  # identical params
    assert sum(errs) == 0, f"leg2 dropped {sum(errs)} request(s)"

    # ---- leg 3: bitwise replay oracle against every replica
    rng = np.random.default_rng(99)
    for r in fleet.replicas:
        for probe in range(2):
            ids = [int(i) for i in rng.integers(0, v, size=3)]
            seed = 1234 + probe
            resp = json.loads(httpc.fetch(
                r.predict_url,
                data=json.dumps(
                    {"node_ids": ids, "replay_seed": seed}
                ).encode("utf-8"),
            ))
            assert resp.get("replay") is True, resp
            got = np.asarray(resp["values"], dtype=np.dtype(resp["dtype"]))
            gen = oracle.sampler.rng
            saved = gen.bit_generator.state
            gen.bit_generator.state = np.random.default_rng(
                seed).bit_generator.state
            try:
                want = oracle.predict(np.asarray(ids, dtype=np.int64))
            finally:
                gen.bit_generator.state = saved
            assert np.array_equal(got, want), (
                f"{r.rid} diverged from the promoted-ckpt oracle on {ids}"
            )

    stats = fleet.stats()
    assert stats["shed"] == 0, stats
    assert stats["requests"] >= 300, stats
finally:
    fleet.close()

evs = []
for p in sorted(glob.glob(f"{XH}/obs/*.jsonl")):
    for line in open(p, encoding="utf-8"):
        if line.strip():
            evs.append(json.loads(line))
assert schema.validate_stream(evs) == len(evs)
assert not [e for e in evs if e["event"] == "shed"], "fleet shed requests"
losses = [e for e in evs if e["event"] == "target_loss"]
assert len(losses) == 1, f"want exactly 1 target_loss, got {len(losses)}"
restarts = [e for e in evs if e["event"] == "recovery"
            and e.get("action") == "restart"]
assert len(restarts) == 1 and restarts[0]["replica"] == "r1", restarts
rollouts = [e for e in evs if e["event"] == "rollout"]
assert len(rollouts) == 1 and rollouts[0]["verdict"] == "promoted", rollouts
drift = [e for e in evs if e["event"] == "model_drift"
         and e.get("source") == "canary"]
assert len(drift) == 1 and drift[0]["drift"] <= drift[0]["threshold"], drift

rows = [r for r in ledger.read_rows(f"{XH}/ledger") if r["kind"] == "fleet"]
assert rows, "no kind=fleet ledger rows"
p99s = [r["hist_quantiles"].get("serve.latency_ms", {}).get("p99")
        for r in rows]
first = next((i for i, q in enumerate(p99s) if q is not None), None)
assert first is not None, "merged p99 never established in the ledger"
broken = [i for i, q in enumerate(p99s[first:], first) if q is None]
assert not broken, (
    f"merged-p99 trajectory broke at poll row(s) {broken[:5]} "
    "(the rollout drain must keep the merge continuous)"
)
print(
    f"crosshost gate: SIGKILL -> 1 target_loss + supervised restart of "
    f"{restarts[0]['replica']}, 0/300+ shed; rollout promoted (canary "
    f"disagreement 0.0, 3 drain/restarts) under load; replay oracle "
    f"bitwise over 6 probes; {len(rows)} fleet ledger rows, p99 unbroken "
    f"from row {first}"
)
EOF
then
  grep "crosshost gate:" /tmp/_t1_xh.log
else
  crosshost_rc=$?
  tail -40 /tmp/_t1_xh.log
fi
if [ "$crosshost_rc" -ne 0 ]; then
  echo "CROSSHOST_GATE=FAIL (rc=$crosshost_rc)"
else
  echo "CROSSHOST_GATE=OK"
fi

# ADVISORY canary-reject leg: a deliberately drifted candidate (float
# leaves rescaled, digests valid so preflight PASSES) offered to a live
# 2-replica fleet via the serve_router CLI must be refused by the canary
# gate — exit 3, one rollout record verdict=canary_reject, ZERO replicas
# restarted, and the fleet still serving its original checkpoint.
xh_adv_rc=0
if [ "$crosshost_rc" -eq 0 ]; then
  rm -rf /tmp/_t1_xh_adv
  mkdir -p /tmp/_t1_xh_adv
  JAX_PLATFORMS=cpu timeout -k 10 120 python - >> /tmp/_t1_xh.log 2>&1 <<'EOF' || xh_adv_rc=$?
import numpy as np

from neutronstarlite_tpu.utils import checkpoint as ck

src, dst = "/tmp/_t1_xh/ckpt_v1", "/tmp/_t1_xh/ckpt_drift"
step, step_dir = ck.list_steps(src)[-1]
manifest, status, arrays = ck.verify_step_dir(step_dir)
state = {}
for name, info in manifest["trees"].items():
    leaves = []
    for i in range(info["n_leaves"]):
        a = arrays[f"{name}.{i}"]
        if np.issubdtype(a.dtype, np.floating):
            a = (a * 1.5 + 0.25).astype(a.dtype)  # real drift, valid digest
        leaves.append(a)
    state[name] = leaves
ck.save_checkpoint(dst, state, step=step)
EOF
  if [ "$xh_adv_rc" -eq 0 ]; then
    JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_xh_adv/obs NTS_NO_NATIVE=1 \
      NTS_SAMPLE_WORKERS=0 timeout -k 10 600 \
      python -m neutronstarlite_tpu.tools.serve_router \
      configs/serve_fleet_smoke.cfg /tmp/_t1_xh/ckpt_v1 --replicas 2 \
      --poll 0.3 --polls 3 --rollout /tmp/_t1_xh/ckpt_drift \
      --rollout-after 1 --spawn-dir /tmp/_t1_xh_adv/spawn \
      >> /tmp/_t1_xh.log 2>&1
    router_rc=$?
    [ "$router_rc" -eq 3 ] || xh_adv_rc=1
    if [ "$xh_adv_rc" -eq 0 ]; then
      JAX_PLATFORMS=cpu python - >> /tmp/_t1_xh.log 2>&1 <<'EOF' || xh_adv_rc=$?
import glob, json

evs = []
for p in sorted(glob.glob("/tmp/_t1_xh_adv/obs/*.jsonl")):
    for line in open(p, encoding="utf-8"):
        if line.strip():
            evs.append(json.loads(line))
rollouts = [e for e in evs if e["event"] == "rollout"]
assert len(rollouts) == 1, rollouts
r = rollouts[0]
assert r["verdict"] == "canary_reject", r
assert r["restarted"] == 0 and r["rolled_back"] == 0, r
drift = [e for e in evs if e["event"] == "model_drift"
         and e.get("source") == "canary"]
assert drift and drift[0]["drift"] > drift[0]["threshold"], drift
print(
    f"canary-reject leg: drifted candidate refused "
    f"(disagreement {drift[0]['drift']:.4f} > tol "
    f"{drift[0]['threshold']}), 0 replicas restarted, router exit 3"
)
EOF
    fi
  fi
  [ "$xh_adv_rc" -eq 0 ] && grep "canary-reject leg:" /tmp/_t1_xh.log
fi
echo "CROSSHOST_CANARY_GATE=rc$xh_adv_rc (advisory unless NTS_CI_MICRO_FATAL=1)"
if [ "${NTS_CI_MICRO_FATAL:-0}" = "1" ] && [ "$xh_adv_rc" -ne 0 ]; then
  crosshost_rc=$xh_adv_rc
fi

# ---- trace fabric gate (ISSUE 20) ------------------------------------------
# STRUCTURAL (hard): distributed request tracing across a REAL 3-process
# fleet — router + spawned serve children over sockets — under open-loop
# load with one replica SIGKILL'd mid-run:
# (1) the merged Chrome trace (trace_timeline --fleet: clock-pair join,
#     NTP-bounded offsets) validates, every process on its own pid;
# (2) >=95% of ok-answered requests form COMPLETE chains (fleet_request
#     -> predict_post -> predict_handler -> request -> execute stage),
#     each carrying graph_seq/model_seq freshness lineage;
# (3) the killed replica's owed requests re-route, not shed: suspect +
#     re_route spans present, ZERO shed spans anywhere;
# (4) per complete chain the replica stage sums reproduce the client
#     latency within the reported clock-skew bound (router_overhead_ms
#     never more negative than 2x the NTP bound).
# The trace env (NTS_TRACE/NTS_METRICS_DIR) must reach the children via
# the pinned launch recipes — no per-child env plumbing here.
trace_rc=0
rm -rf /tmp/_t1_trace
mkdir -p /tmp/_t1_trace
if JAX_PLATFORMS=cpu NTS_TRACE=1 NTS_METRICS_DIR=/tmp/_t1_trace/obs \
    NTS_NO_NATIVE=1 NTS_SAMPLE_WORKERS=0 \
    NTS_SLO_SPEC='serve_p99_ms<=5000@30s' \
    timeout -k 10 900 python - > /tmp/_t1_trace.log 2>&1 <<'EOF'
import glob, os, signal, threading, time

from neutronstarlite_tpu.serve.crosshost import CrossHostFleet
from neutronstarlite_tpu.tools import trace_timeline as tt
from neutronstarlite_tpu.tools.serve_bench import (
    ensure_checkpoint, run_open_loop,
)
from neutronstarlite_tpu.utils.config import InputInfo

TR = "/tmp/_t1_trace"
cfg_path = "configs/serve_fleet_smoke.cfg"
cfg = InputInfo.read_from_cfg_file(cfg_path)
base_dir = os.path.dirname(os.path.abspath(cfg_path))
cfg.checkpoint_dir = f"{TR}/ckpt"
ensure_checkpoint(cfg, base_dir, cfg.checkpoint_dir, train=True)

fleet = CrossHostFleet.spawn(
    cfg_path, cfg.checkpoint_dir, 3, spawn_dir=f"{TR}/spawn",
    poll_s=0.25, miss_k=2,
)
try:
    assert fleet.tracer.enabled, "router tracer off despite NTS_TRACE=1"
    for r in fleet.replicas:
        env = r.recipe.env()
        assert env.get("NTS_TRACE") == "1" and env.get("NTS_METRICS_DIR"), (
            f"{r.rid}: launch recipe did not pin the trace env: {env}"
        )
    out = {}
    t = threading.Thread(target=lambda: out.update(
        e1=run_open_loop(fleet, cfg.vertices, 150, 60.0, 1, 7)))
    t.start()
    time.sleep(0.5)
    # kill the STICKY target: least_burn + hysteresis pins the stream to
    # one replica, so killing it guarantees an owed in-flight request
    # hits the dead socket -> suspect + re_route on the router (a
    # non-sticky victim would only ever surface as a hub-poll loss)
    vidx = fleet._sticky if fleet._sticky is not None else 0
    victim = fleet.replicas[vidx]
    victim.proc.send_signal(signal.SIGKILL)
    t.join(timeout=300.0)
    assert out.get("e1") == 0, f"dropped {out.get('e1')} request(s)"
    deadline = time.time() + 60.0
    while time.time() < deadline and (
        victim.restarts == 0 or fleet.hub.targets[vidx].lost
    ):
        time.sleep(0.2)
    assert victim.restarts == 1, "SIGKILL'd replica never respawned"
finally:
    fleet.close()

paths = sorted(glob.glob(f"{TR}/obs/*.jsonl"))
streams = tt.load_streams(paths, fleet=True)
assert streams, "no span streams under NTS_METRICS_DIR"
# leg 1: merged Chrome trace validates, one pid per process
trace = tt.chrome_trace(streams)
n_chrome = tt.validate_chrome_trace(trace)
assert n_chrome > 0
assert len({st.pid for st in streams}) == len(streams)
bounds = [st.skew_bound for st in streams if st.skew_bound is not None]
assert bounds, "clock-pair alignment reached no stream"

merged = [e for st in streams for e in st.events]
rep = tt.request_tracing_report(merged)
assert rep is not None, "no request traces in the merged streams"
# leg 2: complete chains + freshness lineage
assert rep["n_ok"] >= 140, rep
assert rep["complete_frac"] >= 0.95, (
    f"complete_chain_frac {rep['complete_frac']:.3f} < 0.95 "
    f"({rep['n_complete']}/{rep['n_ok']})"
)
assert rep["graph_seqs"] and rep["model_seqs"], rep
# leg 3: the kill shows up as suspect + re_route, never as a shed
assert rep["suspects"] >= 1 and rep["reroutes"] >= 1, rep
assert rep["sheds"] == 0, f"fleet shed {rep['sheds']} traced request(s)"
# leg 4: stage sums reproduce client latency within the skew bound
tol_ms = 2.0 * max(bounds) * 1000.0 + 1.0
worst = None
for c in rep["chains"]:
    if not c["complete"]:
        continue
    oh = c["router_overhead_ms"]
    assert oh >= -tol_ms, (
        f"{c['trace_id']}: replica stage sum exceeds client latency "
        f"by {-oh:.3f} ms (> {tol_ms:.3f} ms skew tolerance)"
    )
    assert oh <= c["total_ms"], c
    worst = oh if worst is None else max(worst, oh)
print(
    f"trace fabric gate: {rep['n_complete']}/{rep['n_ok']} complete "
    f"chains ({rep['complete_frac'] * 100:.1f}%) over {len(streams)} "
    f"process streams, {rep['suspects']} suspect + {rep['reroutes']} "
    f"re_route / 0 shed after SIGKILL, router overhead p99 "
    f"{rep['router_overhead_p99_ms']:.3f} ms (worst {worst:.3f} ms, "
    f"skew tol {tol_ms:.3f} ms), {n_chrome} chrome events"
)
EOF
then
  grep "trace fabric gate:" /tmp/_t1_trace.log
else
  trace_rc=$?
  tail -40 /tmp/_t1_trace.log
fi
if [ "$trace_rc" -ne 0 ]; then
  echo "TRACE_FABRIC_GATE=FAIL (rc=$trace_rc)"
else
  echo "TRACE_FABRIC_GATE=OK"
fi

# ---- streaming graph gate (ISSUE 18) ---------------------------------------
# STRUCTURAL (hard): a 2-writer delta stream into a LIVE serving fleet —
# (1) after consuming the log, the local engine's graph digest equals a
#     fresh deterministic replay from the base graph AND the log's own
#     recorded head digest (the multi-writer bitwise oracle);
# (2) the in-margin vertex appends apply with compile_counts IDENTICAL
#     to warmup — ZERO AOT recompiles (the capacity-margin contract);
# (3) two spawned replicas tail the same log via NTS_STREAM_LOG, and a
#     /predict replay probe touching an APPENDED vertex answers bitwise
#     what the local streamed engine answers;
# (4) one fine-tune drain over the accumulated dirty region checkpoints
#     through the digest-verified path and reaches a PROMOTED rollout
#     record through the canary-gated fleet rollout. NTS_CANARY_TOL is
#     loosened here because a fine-tune legitimately moves logits — the
#     canary's adversarial teeth are proven by CROSSHOST_CANARY_GATE.
stream_rc=0
rm -rf /tmp/_t1_stream
mkdir -p /tmp/_t1_stream
if JAX_PLATFORMS=cpu NTS_METRICS_DIR=/tmp/_t1_stream/obs NTS_NO_NATIVE=1 \
    NTS_SAMPLE_WORKERS=0 NTS_STREAM_LOG=/tmp/_t1_stream/log \
    NTS_STREAM_VERTEX_MARGIN=4 NTS_STREAM_POLL_S=0.2 NTS_CANARY_TOL=5 \
    timeout -k 10 900 python - > /tmp/_t1_stream.log 2>&1 <<'EOF'
import glob, json, os, time

import numpy as np

from neutronstarlite_tpu.graph.digest import graph_digest
from neutronstarlite_tpu.models import get_algorithm
from neutronstarlite_tpu.obs import httpc, schema
from neutronstarlite_tpu.serve.crosshost import CrossHostFleet
from neutronstarlite_tpu.serve.delta import GraphDelta
from neutronstarlite_tpu.serve.engine import InferenceEngine
from neutronstarlite_tpu.stream.finetune import FineTuneWorker
from neutronstarlite_tpu.stream.ingest import StreamIngestor
from neutronstarlite_tpu.stream.log import DeltaLog
from neutronstarlite_tpu.utils.config import InputInfo

ST = "/tmp/_t1_stream"
cfg_path = "configs/serve_fleet_smoke.cfg"
cfg = InputInfo.read_from_cfg_file(cfg_path)
base_dir = os.path.dirname(os.path.abspath(cfg_path))
cfg.checkpoint_dir = f"{ST}/ckpt_base"
tk = get_algorithm(cfg.algorithm)(cfg, base_dir=base_dir)
tk.init_graph()
tk.init_nn()
tk.run()  # trained params stay live for the fine-tune drain below

base_graph = tk.host_graph
eng = InferenceEngine(tk, cfg.checkpoint_dir, rng=np.random.default_rng(0))
ing = StreamIngestor([eng])  # margin + dirty mode from the gate env
ing.arm()  # BEFORE warmup: the ladder compiles on the padded aval
eng.warmup()
counts0 = dict(eng.compile_counts)

# the 2-writer stream: two in-margin vertex appends + edge churn
fdim = int(np.asarray(tk.feature).shape[1])
dlog = DeltaLog(f"{ST}/log", base_graph)
rng = np.random.default_rng(7)
v = base_graph.v_num
for i in range(2):
    feat = (rng.standard_normal((1, fdim)) * 0.1).astype(np.float32)
    dlog.writer("w1").stage(GraphDelta.edges(
        add=[(7, v), (v, 11)], add_vertices=1, add_features=feat,
    ))
    dlog.writer("w2").stage(GraphDelta.edges(
        add=[(int(rng.integers(0, v)), int(rng.integers(0, v)))
             for _ in range(4)],
    ))
    dlog.commit()
    v += 1

applied = ing.consume(f"{ST}/log")
assert [e.seq for e in applied] == [1, 2, 3, 4], applied
# leg 1: digest at seq N == a fresh deterministic replay from the base
last = None
for _seq, g2 in dlog.iter_graphs(base_graph):
    last = g2
assert graph_digest(last) == dlog.head_digest == eng.graph_digest()
# leg 2: zero AOT recompiles across the in-margin appends
assert dict(eng.compile_counts) == counts0, (eng.compile_counts, counts0)
assert eng.sampler.graph.v_num == base_graph.v_num + 2

fleet = CrossHostFleet.spawn(
    cfg_path, f"{ST}/ckpt_base", 2, spawn_dir=f"{ST}/spawn", poll_s=0.25,
)
try:
    # leg 3: both replicas tail the log — wait until each one's
    # nts_stream_head_seq gauge reaches the log head (a probe racing
    # the tail thread would exercise the pre-delta graph), then ONE
    # replay probe touching the FIRST APPENDED vertex must answer
    # bitwise what the local streamed engine answers
    ids = [base_graph.v_num, 7, 11]
    for r in fleet.replicas:
        deadline = time.time() + 120.0
        caught_up = False
        while time.time() < deadline:
            try:
                text = httpc.fetch(f"{r.base_url}/metrics")
                if any(line.startswith("nts_stream_head_seq")
                       and float(line.split()[-1]) >= 4
                       for line in text.splitlines()):
                    caught_up = True
                    break
            except Exception:
                pass
            time.sleep(0.3)
        assert caught_up, (
            f"{r.rid} never applied the stream through seq 4 "
            "(stream tail dead?)"
        )
        resp = json.loads(httpc.fetch(
            r.predict_url,
            data=json.dumps(
                {"node_ids": ids, "replay_seed": 77}
            ).encode("utf-8"),
        ))
        got = np.asarray(resp["values"], dtype=np.dtype(resp["dtype"]))
        gen = eng.sampler.rng
        saved = gen.bit_generator.state
        gen.bit_generator.state = np.random.default_rng(
            77).bit_generator.state
        try:
            want = eng.predict(np.asarray(ids, dtype=np.int64))
        finally:
            gen.bit_generator.state = saved
        assert np.array_equal(got, want), (
            f"{r.rid} diverged from the local streamed engine on {ids}"
        )

    # leg 4: one fine-tune drain -> digest-verified checkpoint -> the
    # canary-gated rollout promotes it into the serving fleet
    worker = FineTuneWorker(tk, ing, f"{ST}/ckpt_ft",
                            publish=fleet.rollout, seeds_per_round=32,
                            seed=3)
    summary = worker.drain_once()
    assert summary is not None and np.isfinite(summary["loss"]), summary
    assert summary["verdict"] == "promoted", summary
    assert worker.staleness() == 0
finally:
    fleet.close()

evs = []
for p in sorted(glob.glob(f"{ST}/obs/*.jsonl")):
    for line in open(p, encoding="utf-8"):
        if line.strip():
            evs.append(json.loads(line))
assert schema.validate_stream(evs) == len(evs)
commits = [e for e in evs if e["event"] == "delta_commit"]
# 4 from the local ingestor + 4 per replica tail (and re-applies after
# the rollout restarts) — at least the local 4 must be typed records
assert len(commits) >= 4, f"want >=4 delta_commit records, got {len(commits)}"
fts = [e for e in evs if e["event"] == "finetune_round"]
assert len(fts) == 1 and fts[0]["verdict"] == "promoted", fts
rollouts = [e for e in evs if e["event"] == "rollout"]
assert len(rollouts) == 1 and rollouts[0]["verdict"] == "promoted", rollouts
print(
    f"stream gate: 2-writer log seq 4 digest == fresh replay, 0 AOT "
    f"recompiles across in-margin appends, 2 replicas bitwise on the "
    f"appended vertex, fine-tune ckpt step {fts[0]['ckpt_step']} "
    f"rollout promoted ({len(commits)} delta_commit records)"
)
EOF
then
  grep "stream gate:" /tmp/_t1_stream.log
else
  stream_rc=$?
  tail -40 /tmp/_t1_stream.log
fi
if [ "$stream_rc" -ne 0 ]; then
  echo "STREAM_GATE=FAIL (rc=$stream_rc)"
else
  echo "STREAM_GATE=OK"
fi

# ADVISORY bitset-vs-exact dirty-closure timing leg: the approximate
# tracker exists to be CHEAPER than the exact out-closure at high delta
# rates; here it must stay a measured superset of exact on every delta
# (the hard invariant, also pinned in tests/test_stream_ingest.py) and
# plan deltas in no more than ~2x the exact path's time on a 20k-vertex
# RMAT graph (generated, tools/graph_gen).
stream_adv_rc=0
if [ "$stream_rc" -eq 0 ]; then
  JAX_PLATFORMS=cpu timeout -k 10 300 python - >> /tmp/_t1_stream.log 2>&1 <<'EOF' || stream_adv_rc=$?
import time

import numpy as np

from neutronstarlite_tpu.graph.storage import build_graph
from neutronstarlite_tpu.serve.delta import GraphDelta, plan_delta
from neutronstarlite_tpu.stream.ingest import BitsetDirtyTracker
from neutronstarlite_tpu.tools.graph_gen import synth_edges

V, E, HOPS = 20000, 120000, 2
src, dst = synth_edges("rmat", V, E, seed=1)
g = build_graph(src, dst, V, use_native=False)
rng = np.random.default_rng(2)
deltas = [
    GraphDelta.edges(add=[
        (int(rng.integers(0, V)), int(rng.integers(0, V)))
        for _ in range(8)
    ])
    for _ in range(30)
]

t0 = time.perf_counter()
exact = [plan_delta(g, d, HOPS).dirty for d in deltas]
t_exact = time.perf_counter() - t0

tracker = BitsetDirtyTracker(g, buckets=4096)
t0 = time.perf_counter()
approx = []
for d in deltas:
    tracker.observe_delta(d)
    approx.append(plan_delta(g, d, HOPS,
                             dirty_closure=tracker.closure).dirty)
t_bitset = time.perf_counter() - t0

for i, (ex, ap) in enumerate(zip(exact, approx)):
    missed = np.setdiff1d(ex, ap)
    assert missed.size == 0, (
        f"delta {i}: bitset closure MISSED dirty vertices {missed[:5]}"
    )
fp = float(np.mean([
    (len(ap) - len(ex)) / max(len(ap), 1)
    for ex, ap in zip(exact, approx)
]))
print(
    f"stream timing leg: exact {t_exact * 1e3:.0f} ms vs bitset "
    f"{t_bitset * 1e3:.0f} ms over {len(deltas)} deltas on a {V}-vertex "
    f"rmat graph (mean fp {fp:.3f})"
)
assert t_bitset <= max(t_exact * 2.0, 0.05), (t_bitset, t_exact)
EOF
  [ "$stream_adv_rc" -eq 0 ] && grep "stream timing leg:" /tmp/_t1_stream.log
fi
echo "STREAM_TIMING_GATE=rc$stream_adv_rc (advisory unless NTS_CI_MICRO_FATAL=1)"
if [ "${NTS_CI_MICRO_FATAL:-0}" = "1" ] && [ "$stream_adv_rc" -ne 0 ]; then
  stream_rc=$stream_adv_rc
fi

[ "$rc" -eq 0 ] && rc=$fused_rc
[ "$rc" -eq 0 ] && rc=$samp_rc
[ "$rc" -eq 0 ] && rc=$zeroh2d_rc
[ "$rc" -eq 0 ] && rc=$elastic_rc
[ "$rc" -eq 0 ] && rc=$tune_rc
[ "$rc" -eq 0 ] && rc=$mesh_rc
[ "$rc" -eq 0 ] && rc=$obs_rc
[ "$rc" -eq 0 ] && rc=$ledger_rc
[ "$rc" -eq 0 ] && rc=$fleet_rc
[ "$rc" -eq 0 ] && rc=$numerics_rc
[ "$rc" -eq 0 ] && rc=$hub_rc
[ "$rc" -eq 0 ] && rc=$crosshost_rc
[ "$rc" -eq 0 ] && rc=$trace_rc
[ "$rc" -eq 0 ] && rc=$stream_rc
exit $rc
