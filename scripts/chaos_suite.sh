#!/usr/bin/env bash
# Chaos tier: run every fault-injection test (pytest -m chaos) with a FIXED
# seed so a failure replays exactly (docs/resilience.md).
#
# The fast chaos cases already ride tier-1 (`-m 'not slow'` picks them up);
# this script is the dedicated lane: chaos tests ONLY, slow ones included,
# with the seed pinned and printed so CI logs carry the repro line.
#
# Usage: scripts/chaos_suite.sh [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."

SEED="${CDT_CHAOS_SEED:-42}"
echo "[chaos] fixed seed: ${SEED} (override with CDT_CHAOS_SEED)"
echo "[chaos] repro: CDT_CHAOS_SEED=${SEED} scripts/chaos_suite.sh $*"

# Stage 0 — machine-checked invariants (ISSUE 12 + 20, docs/lint.md):
# cdtlint v2 over the package against the committed baseline — the
# per-function rules (L001/A001/D001/K001/J001) plus the project-wide
# flow rules on the call graph + taint engine (A002 transitive
# async-blocking, L002 lock-held-across-await, D002 interprocedural
# nondeterminism taint, W001 wire/route<->docs/api.md contract). Fails
# on any non-baselined finding AND on a stale or unjustified baseline
# entry (the baseline only shrinks). Then re-run the stage-1 chaos
# event under the runtime lock-order detector (CDT_LOCK_ORDER=1):
# every lock the event path takes records its acquisition order, and an
# inversion fails the test loudly instead of deadlocking a future run.
echo "[chaos] stage 0: cdtlint v2 (call-graph + taint invariants) + lock-order detector"
python -m comfyui_distributed_tpu.lint
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" CDT_LOCK_ORDER=1 \
    python -m pytest tests/ -q -m chaos -k "warm_restarted or lock_order" \
    -p no:cacheprovider --continue-on-collection-errors "$@"

# Stage 1 — seeded rolling-restart event (ISSUE 6): a worker dies
# mid-job holding work; its warm restart (shared compile cache + shape
# catalog) must rejoin with a pure cache-hit warmup pass and the job
# must complete with nothing dropped or dead-lettered.
echo "[chaos] stage 1: rolling-restart event (warm worker rejoin)"
# (filter matches test_warm_restarted_worker_rejoins_without_dropping_jobs;
# the old "rolling_restart" pattern matched nothing and rc=5 aborted the
# whole suite under set -e)
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" \
    python -m pytest tests/ -q -m chaos -k "warm_restarted" \
    -p no:cacheprovider --continue-on-collection-errors "$@"

# Stage 2 — seeded front-door overload event (ISSUE 9, docs/serving.md):
# 4× capacity of seeded mixed-tenant load against a pinned-low shed
# threshold. Asserted: surplus requests get deterministic 429s with
# Retry-After (never hangs), queue depth stays bounded under the
# threshold, zero admitted-job loss, and both tenants complete work.
echo "[chaos] stage 2: front-door overload (shed 429s, zero admitted loss)"
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" \
    python -m pytest tests/ -q -m chaos -k "overload" \
    -p no:cacheprovider --continue-on-collection-errors "$@"

# Stage 3 — the rest of the chaos tier
echo "[chaos] stage 3: full chaos tier"
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" \
    python -m pytest tests/ -q -m chaos \
    -k "not warm_restarted and not overload and not scale_event and not cache_corrupt and not mesh_drain and not preempt and not decode_worker and not fleet_shard" \
    -p no:cacheprovider --continue-on-collection-errors "$@"

# Stage 4 — seeded scale events under live load (ISSUE 10,
# docs/elasticity.md): (a) the chaos-marked acceptance test — a mixed
# two-job run that scales up mid-job (steal pickup), drains one worker
# (deadline handback), and rolling-restarts another (drain → undrain),
# asserting bit-identical outputs vs the static fleet, zero dead-letters,
# and no breaker opening for any intentional departure; (b) load_smoke
# --churn — seeded drain/kill/restart events interleaved with the
# mixed-tenant serving load, exiting 1 on any admitted-job loss or
# unbounded queue depth.
echo "[chaos] stage 4: elastic scale events (scale-up / drain / rolling restart)"
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" CDT_STEAL_SEED="${SEED}" \
    python -m pytest tests/ -q -m chaos -k "scale_event" \
    -p no:cacheprovider --continue-on-collection-errors "$@"
echo "[chaos] stage 4b: churn load smoke (zero admitted-job loss)"
env JAX_PLATFORMS=cpu PYTHONPATH="$(pwd)" \
    CDT_CONFIG_PATH="$(mktemp -d)/config.json" \
    python scripts/load_smoke.py --in-process --churn --n 12 \
    --concurrency 8 --seed "${SEED}"

# Stage 5 — persisted-cache corruption under live load (ISSUE 11,
# docs/caching.md): a persisted result-cache entry is byte-flipped while
# a duplicate-heavy load runs. Asserted: the checksum rejects the entry
# LOUDLY (cdt_cache_corrupt_total), the request recomputes, every served
# image is bit-identical to the uncorrupted reference, and zero admitted
# jobs are lost. Then the dup-rate smoke: a seeded duplicate/near-dup
# mix through the real front door, exit 1 on any admitted-job loss.
echo "[chaos] stage 5: cache corruption under load (zero wrong-byte serves)"
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" \
    python -m pytest tests/ -q -m chaos -k "cache_corrupt" \
    -p no:cacheprovider --continue-on-collection-errors "$@"
echo "[chaos] stage 5b: duplicate-mix load smoke (dup-rate 0.5)"
env JAX_PLATFORMS=cpu PYTHONPATH="$(pwd)" \
    CDT_CONFIG_PATH="$(mktemp -d)/config.json" \
    CDT_CACHE_DIR="$(mktemp -d)" \
    python scripts/load_smoke.py --in-process --n 12 --dup-rate 0.5 \
    --concurrency 8 --seed "${SEED}"

# Stage 6 — executed mesh tier under drain (ISSUE 13,
# docs/parallelism.md): a worker drains MID mesh-tier batched job (each
# tile runs the dp×tp microbatched program) under the runtime
# lock-order detector. Asserted: bit-identical completion vs the
# uninterrupted reference, zero dead-letters, no breaker opens. The
# excluded-strategy filter note: "mesh_drain" selects the chaos-marked
# TestChaosMeshDrain case in tests/test_mesh_serving.py (stage 3's
# blanket run excludes it via the filter below staying in sync).
echo "[chaos] stage 6: mesh-tier drain (bit-identical, lock-order armed)"
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" CDT_LOCK_ORDER=1 \
    python -m pytest tests/ -q -m chaos -k "mesh_drain" \
    -p no:cacheprovider --continue-on-collection-errors "$@"

# Stage 7 — step-granular preemption (ISSUE 14, docs/preemption.md):
# (a) the chaos-marked acceptance tests under the runtime lock-order
# detector — a job preempted mid-denoise and resumed locally AND on a
# different worker is bit-identical to an uninterrupted run (zero
# dead-letters, no breaker opens), a preemption landing mid mesh-tier
# batch traffic records zero lock inversions, and a checkpoint that
# cannot restore dead-letters after its bounded retries then completes
# from scratch; (b) load_smoke --preempt — a long video-class job
# churns under a seeded interactive workload, exit 1 unless the long
# job completes, at least one preemption fired, and interactive p99
# stays bounded (the full-residual failure mode this subsystem
# removes). The compile cache dir keeps re-runs warm so one-time
# compiles don't pollute the latency signal.
echo "[chaos] stage 7: preemption (bit-identical resume, bounded interactive p99)"
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" CDT_LOCK_ORDER=1 \
    python -m pytest tests/ -q -m chaos -k "preempt" \
    -p no:cacheprovider --continue-on-collection-errors "$@"
echo "[chaos] stage 7b: preempt load smoke (interactive p99 under a long job)"
env JAX_PLATFORMS=cpu PYTHONPATH="$(pwd)" \
    CDT_CONFIG_PATH="$(mktemp -d)/config.json" \
    JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-/tmp/cdt_xla_cache_chaos}" \
    python scripts/load_smoke.py --in-process --preempt --n 6 \
    --concurrency 4 --seed "${SEED}"

# Stage 8 — stage-split serving under decode-worker death (ISSUE 15,
# docs/stages.md): (a) the chaos-marked acceptance under the runtime
# lock-order detector — a decode-pool worker is killed while holding a
# BATCH of transferred latents; the latents re-dispatch to a surviving
# decoder, every member completes BIT-identically to the fused path,
# zero dead-letters, no breaker opens, zero lock inversions; (b)
# load_smoke --stages — the mixed-tenant load through the three pools,
# exit 1 on any admitted-job loss or a stage backlog past its shed
# threshold. The compile cache dir keeps the latent/decode programs
# warm across re-runs.
echo "[chaos] stage 8: stage-split serving (decode-worker death, bounded backlogs)"
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" CDT_LOCK_ORDER=1 \
    python -m pytest tests/ -q -m chaos -k "decode_worker" \
    -p no:cacheprovider --continue-on-collection-errors "$@"
echo "[chaos] stage 8b: stages load smoke (three pools, bounded backlogs)"
env JAX_PLATFORMS=cpu PYTHONPATH="$(pwd)" \
    CDT_CONFIG_PATH="$(mktemp -d)/config.json" \
    JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-/tmp/cdt_xla_cache_chaos}" \
    python scripts/load_smoke.py --in-process --stages --n 12 \
    --concurrency 8 --seed "${SEED}"

# Stage 9 — fleet cache under shard-owner death (ISSUE 17,
# docs/caching.md): (a) the chaos-marked acceptance under the runtime
# lock-order detector — two real controllers on one consistent-hash
# ring; a duplicate is served REMOTELY from the shard owner's tier,
# then the owner is killed mid dup-heavy load. The survivor recomputes
# BIT-identically (the fallback ladder's last rung), zero admitted-job
# loss, and the dead owner's breaker holds no cache-probe evidence
# (probes are scavenging, not health checks); (b) load_smoke --fleet —
# duplicates routed to the worker that did NOT compute the original,
# exit 1 unless the cross-worker hit rate beats the per-host
# (CDT_FLEET_CACHE=0) baseline.
echo "[chaos] stage 9: fleet cache (shard-owner death, cross-worker serves)"
env JAX_PLATFORMS=cpu CDT_CHAOS_SEED="${SEED}" CDT_LOCK_ORDER=1 \
    python -m pytest tests/ -q -m chaos -k "fleet_shard" \
    -p no:cacheprovider --continue-on-collection-errors "$@"
echo "[chaos] stage 9b: fleet load smoke (cross-worker hit rate beats per-host)"
env JAX_PLATFORMS=cpu PYTHONPATH="$(pwd)" \
    CDT_CONFIG_PATH="$(mktemp -d)/config.json" \
    JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-/tmp/cdt_xla_cache_chaos}" \
    python scripts/load_smoke.py --fleet --fleet-n 4 \
    --concurrency 8 --seed "${SEED}"

# Stage 10 — event-loop stall sanitizer (ISSUE 20, docs/lint.md): re-run
# the stage-split and fleet-cache smokes with CDT_LOOP_STALL=1 — every
# asyncio callback is timed (lint/loopstall.py patches Handle._run at
# import) and a sampler thread captures the live stack of any callback
# blocking the loop past CDT_LOOP_STALL_MS. load_smoke exits 1 on ANY
# recorded stall, so the executor discipline the static rules (A001/
# A002) prove on the AST is also proven at runtime under real serving
# load — including blocking work static analysis can't see (C
# extensions, pathological codec inputs). The threshold is held above
# the default: on CI-shared CPU the first-compile XLA callbacks and the
# GIL under 8-way concurrency make sub-100ms guarantees unmeasurable.
echo "[chaos] stage 10: loop-stall sanitizer (stage-split + fleet smokes armed)"
env JAX_PLATFORMS=cpu PYTHONPATH="$(pwd)" \
    CDT_CONFIG_PATH="$(mktemp -d)/config.json" \
    JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-/tmp/cdt_xla_cache_chaos}" \
    CDT_LOOP_STALL=1 CDT_LOOP_STALL_MS="${CDT_LOOP_STALL_MS:-250}" \
    python scripts/load_smoke.py --in-process --stages --n 12 \
    --concurrency 8 --seed "${SEED}"
env JAX_PLATFORMS=cpu PYTHONPATH="$(pwd)" \
    CDT_CONFIG_PATH="$(mktemp -d)/config.json" \
    JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-/tmp/cdt_xla_cache_chaos}" \
    CDT_LOOP_STALL=1 CDT_LOOP_STALL_MS="${CDT_LOOP_STALL_MS:-250}" \
    python scripts/load_smoke.py --fleet --fleet-n 4 \
    --concurrency 8 --seed "${SEED}"
