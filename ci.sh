#!/usr/bin/env bash
# Tier-1 CI gate: catches invariant violations (JAX API drift, serving
# clock leaks, bare asserts, import-time device probing, kernel-trio /
# fused-kind drift) at PR time. Usage: ./ci.sh [--no-install]
set -euo pipefail
cd "$(dirname "$0")"

# Static-analysis gate FIRST: repro.analysis is stdlib-only, so it runs
# before any pip work. AST-based successor to the old compat-drift /
# serving-clock greps — it also sees aliased imports (`from time import
# monotonic`, `import jax.experimental.shard_map as smap`) and structure
# (bare asserts, import-time jax, kernel.py/ref.py/ops.py trios,
# cache-key hazards, FusedStep-kind exhaustiveness). Rule catalog:
# `python -m repro.analysis.cli --list-rules`; see README "Static
# analysis".
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis.cli src/repro

if [[ "${1:-}" != "--no-install" ]]; then
    python -m pip install -q -r requirements-dev.txt
fi

# Tier-1 verify (ROADMAP.md): the whole suite, quiet, fail-fast off so the
# summary shows every regression.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q

# Second tier-1 leg: force the pure-XLA reference kernel tier, so the
# fallback path deployments without Pallas rely on is exercised in CI — not
# just whatever the probe picked on this machine.
REPRO_KERNEL_BACKEND=xla \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q

# Serving smoke: replay a tiny Poisson trace through the continuous-batching
# server and the looped one-shot path; exits nonzero if their tokens diverge.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_serving --smoke

# Packed-plan smoke: IVIM volume through the compiled PackedPlan path vs the
# unpacked baseline (equivalence is tested; this guards the bench wiring).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_ivim_packed --smoke

# Fused-megakernel smoke: the whole-plan kernels/fused_plan Pallas kernel
# under the interpreter (not just its xla ref), one launch + in-kernel
# moments per chunk; the bench exits nonzero if fused and per-op moments
# diverge.
REPRO_KERNEL_BACKEND=pallas-interpret \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_ivim_packed --smoke --fused

# Fused-decode smoke: the serving decode step as ONE kernels/fused_plan
# launch under the interpreter — the bench exits nonzero if the fused leg
# silently fell back per-op, if fused and per-op decode tokens diverge, or
# if the fused step models no per-token HBM-byte reduction.
REPRO_KERNEL_BACKEND=pallas-interpret \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_serving --smoke --fused

# Quantized-serving smoke: int8 packed weights through the fused Pallas
# kernel (bench_ivim_packed exits nonzero if int8 moments drift past
# tolerance or the modeled int8 fused weight bytes exceed 0.35x fp32) and
# the bf16/int8 KV-cache server legs (bench_serving --quantized exits
# nonzero if their tokens diverge from the f32-cache leg or the bf16 spec
# models no decode HBM-byte reduction). Dispatches are labeled
# kernel_dispatch_total{tier,precision} in the registry snapshot.
REPRO_KERNEL_BACKEND=pallas-interpret \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_serving --smoke --quantized
# (the int8 weight gates ride every bench_ivim_packed run above)

# Mixed-modality + observability smoke: IVIM scans as voxel-chunk work
# items interleaved into the same serving pool as the LM trace, with the
# traced replay exporting its JSONL span log and the Prometheus exposition.
# The bench exits nonzero if the pooled scan moments are not
# bitwise-identical to the direct predict_volume path, if co-resident scans
# perturb the LM tokens, if enabling tracing changes tokens/moments, or if
# it adds jit retraces; the verifier then replays the JSONL into a
# per-request lifecycle state machine and parses the exposition.
obs_dir=$(mktemp -d)
trap 'rm -rf "$obs_dir"' EXIT
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_serving --smoke --mixed \
    --trace-out "$obs_dir/trace.jsonl" --metrics-out "$obs_dir/metrics.prom"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.verify_obs \
    --trace "$obs_dir/trace.jsonl" --metrics "$obs_dir/metrics.prom"

# Chaos smoke: the same trace through the 3-host fault-tolerant router,
# unfaulted and under a seeded FaultPlan that kills a host mid-run. The
# bench exits nonzero if any request is lost or shed, if the scenario
# failed to exercise a host death with retries, or if the recovered tokens
# are not bitwise-identical to the unfaulted run; the verifier then checks
# the faulted run's span log (host-death -> retry -> re-admit lifecycle,
# retry events only inside router.host_death/router.straggler_drain
# spans).
REPRO_KERNEL_BACKEND=pallas-interpret \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_serving --smoke --chaos \
    --chaos-trace-out "$obs_dir/chaos.jsonl"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.verify_obs \
    --trace "$obs_dir/chaos.jsonl"
