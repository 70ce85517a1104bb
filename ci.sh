#!/usr/bin/env bash
# CI entry point (reference parity: .travis.yml:32-37 runs racon_test on
# every build). Default tier runs the full CPU suite (which includes the
# two-shape device-engine test, tests/test_poa.py), the flagship
# device-engine golden (ED vs the reference acceptance value — a gate,
# not a docstring), and the multi-chip dryrun.
#
#   ci.sh          default tier
#   ci.sh --full   additionally runs every opt-in 'ava' golden
#                  (fragment-correction acceptance set)
set -euo pipefail
cd "$(dirname "$0")"

FULL=0
[[ "${1:-}" == "--full" ]] && FULL=1

echo "[ci] contract linter (docs/ANALYSIS.md; fails on non-baselined findings)"
python scripts/lint.py --ci

if [[ "$FULL" == 1 ]]; then
  echo "[ci] pytest (CPU, 8 virtual devices, FULL incl. ava goldens)"
  python -m pytest tests/ -q -m ''
else
  echo "[ci] pytest (CPU, 8 virtual devices)"
  python -m pytest tests/ -q
  echo "[ci] device-engine golden (SAM+FASTQ acceptance, gates ED <= 1317)"
  python -m pytest tests/test_polisher.py -q -m '' \
    -k test_consensus_device_engine_golden_sam_fastq
  echo "[ci] scheduler differential golden (sched vs fixed, SAM+FASTQ)"
  python -m pytest tests/test_polisher.py -q -m '' \
    -k "test_sched_differential_golden and sam_fastq"
  echo "[ci] pipeline differential golden (streamed vs serial, SAM+FASTQ)"
  python -m pytest tests/test_pipeline.py -q -m '' \
    -k "test_pipeline_differential_golden and sam_fastq"
fi

echo "[ci] multi-chip dryrun (8 virtual devices)"
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "[ci] interpreter-mode kernel + dual-column walk smoke"
python -m pytest tests/test_kernels_interpret.py tests/test_colwalk.py \
  -q -m ''

echo "[ci] long-read smoke (20 kb tiled device path, zero native fallbacks)"
python scripts/ultralong_smoke.py

echo "[ci] observability smoke (traced tiny polish + JSONL schema gate)"
python scripts/obs_smoke.py

echo "[ci] pipeline smoke (streamed == serial FASTA + pipe span/gauge gate)"
python scripts/pipeline_smoke.py

echo "[ci] walk overlap smoke (decoupled walk hidden>0, byte-diff vs fused, stall drill)"
python scripts/walk_overlap_smoke.py

echo "[ci] resilience smoke (injected faults + kill-and-resume byte-diff)"
python scripts/resilience_smoke.py

echo "[ci] preemption smoke (2-worker fleet, 3 evictions, steal + merge byte-diff)"
python scripts/preemption_smoke.py

echo "[ci] redo smoke (flagged windows resolve on device, zero host redos, byte-diff)"
python scripts/redo_smoke.py

echo "[ci] fleet obs smoke (2-worker fleet, 1 eviction, aggregate + OpenMetrics gate)"
python scripts/fleet_obs_smoke.py

echo "[ci] failslow smoke (choke-point hangs, stage stall, self-eviction + merge byte-diff)"
python scripts/failslow_smoke.py

echo "[ci] chaos bench smoke (autoscaled fleet, evictions + straggler, makespan bound + byte-diff)"
python scripts/chaos_bench.py --smoke

echo "[ci] ingest smoke (parallel inflate plans, gz+plain 4-way byte-diff, ingest spans validate)"
python scripts/ingest_smoke.py

echo "[ci] server smoke (daemon, 3 jobs/2 tenants, cross-request occupancy > solo, kill+restart byte-diff)"
python scripts/server_smoke.py

echo "[ci] cache smoke (CAS resubmit = zero dispatches, torn-entry drill, CACHE=0 fallback, byte-diff)"
python scripts/cache_smoke.py

echo "[ci] job trace smoke (daemon + 2-worker fleet, ctx handoff, mid-shard kill, 3-process timeline + flight dump)"
python scripts/job_trace_smoke.py

echo "[ci] fleet serve smoke (gateway routing, worker kill, warm pool, standby adoption, byte-diff)"
python scripts/fleet_serve_smoke.py

echo "[ci] ava scale smoke (10k-read kF fleet, 1 eviction, weighted bounds, v2 manifests, byte-diff)"
python scripts/ava_scale_smoke.py

echo "[ci] OK"
