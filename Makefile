# Developer entry points (reference build-system analog, SURVEY.md §2.5 L8).
SHELL := /bin/bash
.PHONY: test t1 t1-faults t1-obs t1-cluster-obs t1-kernels t1-serving t1-serving-faults t1-streaming t1-fleet t1-recsys t1-elastic t1-promotion t1-paged dist multichip clean

test:
	python -m pytest tests/ -x -q

# ROADMAP.md tier-1 verify, verbatim — the no-worse-than-seed gate.
t1:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# Fault-injection suite only (docs/robustness.md): every recovery path —
# decode error, transform-worker death, h2d failure, non-finite loss,
# SIGTERM preemption, SIGKILL-during-checkpoint-write, corrupt checkpoint on
# disk — fired deterministically via BIGDL_FAULT_PLAN / inject_faults().
# These tests are unmarked-slow, so `make t1` runs them too; this target is
# the fast inner loop when working on fault tolerance.
t1-faults:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m faults --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Observability suite only (docs/observability.md): span tracer Chrome-trace
# export, JSONL event log + `bigdl-tpu diag` round trip, metric registry
# (incl. snapshot tear-resistance under concurrent observers), /metrics
# exporter (Prometheus round trip, endpoint concurrency, per-tenant labels,
# zero-alloc when BIGDL_METRICS_PORT unset), request trace-ID propagation +
# tail sampling + `diag --trace`, MFU gauge consistency, SLO breach →
# serving-health transitions, hang-watchdog stall dumps with in-flight
# request context, zero-cost disabled paths. Unmarked-slow, so `make t1`
# runs these too; this is the fast inner loop for obs work.
t1-obs:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m obs --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Cluster-telemetry suite only (docs/observability.md "Cluster aggregation"):
# spool merge + {host=} round-trip, the 2-process gloo drill with the
# SIGKILL-one-host stale degrade, device-memory gauges, /profilez routes,
# access-log → .bdlrec replay. `-m obs` (and make t1) run these too; this
# target is the focused loop.
t1-cluster-obs:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/test_cluster_obs.py -q --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Kernel-equivalence suite only (docs/performance.md "Kernel fusion & memory"):
# fused conv-bn(-relu) vs unfused fp32 bitwise, flat-param SGD/Adam updates vs
# per-leaf, grad-accum M∈{1,2,4} vs M=1 on LeNet, remat policies. Unmarked-slow, so `make t1` runs these too; this target is
# the fast inner loop for kernel work.
t1-kernels:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m kernels --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Online-serving suite only (docs/serving.md): continuous-batching bitwise
# equality vs per-request greedy decode, bucket/padding invariance, slot
# recycling under randomized arrivals, per-slot cache reset/assign, the
# shared request-plane queue, quantized + multi-tenant snapshots. Unmarked-
# slow, so `make t1` runs these too; this is the fast inner loop for
# serving-engine work.
t1-serving:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m serving --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Serving-plane fault injection only (docs/robustness.md "Serving"): engine-
# thread crash + supervisor respawn with bitwise recovery, per-slot non-finite
# guard, prefill faults, decode stalls vs deadlines/watchdog, wedged-shutdown
# detection. Unmarked-slow, so `make t1` runs these too; this is the fast
# inner loop for serving-robustness work.
t1-serving-faults:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m serving_faults --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Streaming-data-plane suite only (docs/performance.md "Streaming & sample
# cache"): window-shuffle determinism + worker-count order invariance,
# checkpointable stream position (mid-epoch SIGTERM resume bitwise), per-host
# shard(), decoded-sample cache build/warm-read/quarantine + cache_read/
# cache_write fault sites. Unmarked-slow, so `make t1` runs these too; this
# target is the fast inner loop for data-plane work.
t1-streaming:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m streaming --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Serving-fleet suite only (docs/serving.md "Fleet"): replica router bitwise
# vs solo engine, zero-lost under scripted replica_down/drain churn, prefix
# KV-cache pool hit/evict determinism (programs ledger stays flat), and
# speculative decoding bitwise vs plain greedy at 0% and 100% acceptance.
# Unmarked-slow, so `make t1` runs these too; this is the fast inner loop
# for fleet work.
t1-fleet:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m fleet --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Sharded-embedding recsys suite only (docs/performance.md "Sharded
# embeddings & sparse updates"): sharded-vs-replicated NCF bitwise under the
# 8-device dryrun mesh, dedup-gather equivalence, sparse-vs-dense optimizer
# equality per method (touched rows exact, untouched bitwise-unchanged),
# padding/id-guard satellites, HR/NDCG device folds, sharded-table
# checkpoint round trip. Unmarked-slow, so `make t1` runs these too; this
# is the fast inner loop for recsys/embedding work.
t1-recsys:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m recsys --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Elastic-checkpointing suite only (docs/robustness.md "Elastic training"):
# sharded snapshot→assemble bitwise round trip, manifest-commits-last
# all-or-nothing (ckpt_async=torn), async-write overlap vs the hard barrier,
# topology-portable resume (2,4)→(4,) with trajectory equality, keep-last-N
# skipping in-flight versions, two-writer version agreement, and the
# host-loss drill (2-process run, one worker SIGKILLed by host_down, the
# survivor re-execs and resumes on the shrunk topology). Unmarked-slow, so
# `make t1` runs these too; this target is the fast inner loop for elastic
# work.
t1-elastic:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m elastic --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Promotion-lifecycle suite only (docs/serving.md "Lifecycle"): registry
# publish/prune/lora-overlay, gate accept/reject (eval crash and NaN metric
# quarantine the candidate, never the trainer), swap-under-load with bitwise
# continuity and a pinned program ledger, the scripted bad-promotion →
# SLO-breach → auto-rollback drill (plan fully fired, served outputs bitwise
# back to the pre-promotion version), LoRA-delta swaps, SnapshotServer
# in-place tenant swap, and trainer→registry publication. Unmarked-slow, so
# `make t1` runs these too; this is the fast inner loop for lifecycle work.
t1-promotion:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m promotion --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# Paged-serving suite only (docs/serving.md "Paged KV cache & disaggregation"):
# page-allocator property storms, the paged-vs-slot-grid bitwise A/B trace,
# pool-exhaustion preemption, the prefill→decode handoff, speculation over
# paged state, and the BIGDL_KV_PAGED=0 rollback switch. Unmarked-slow, so
# `make t1` runs these too; this target is the fast inner loop for paging work.
t1-paged:
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m paged --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

dist:
	bash make-dist.sh

multichip:
	python -m bigdl_tpu.cli dryrun-multichip -n 8

clean:
	rm -rf dist build *.egg-info
