"""Benchmark aggregator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--smoke]

Emits ``name,value,derived`` CSV lines (plus each benchmark's own report).
``--smoke`` runs the serving bench on its tiny CI trace (the other benches
are already CPU-sized).
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny serving trace (CI-sized)")
    args = ap.parse_args()

    from repro import compat
    compat.enable_compilation_cache()
    from benchmarks import (bench_algorithm, bench_ivim_packed, bench_kernels,
                            bench_latency_model, bench_roofline,
                            bench_schedule, bench_serving)

    csv: list[tuple[str, float, str]] = []

    # Provenance: stamp the static-analysis state of the tree these numbers
    # were measured on (checker version + finding count; ci.sh gates the
    # count at 0, so a nonzero here marks the run as off-gate).
    from repro.analysis import __version__ as analysis_version
    from repro.analysis import checker as analysis_checker
    pkg = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"
    findings = analysis_checker.analyze(pkg)
    active = sum(1 for f in findings if not f.suppressed)
    print(f"repro.analysis v{analysis_version}: {active} finding(s), "
          f"{len(findings) - active} suppressed")
    csv.append(("static_analysis_findings", float(active),
                f"repro.analysis v{analysis_version} invariant findings "
                "(gate: 0)"))

    print("=" * 72)
    print("bench_algorithm — paper Figs. 6-7 (RMSE / uncertainty vs SNR)")
    print("=" * 72)
    t0 = time.perf_counter()
    alg = bench_algorithm.run(steps=300)
    csv.append(("fig6_7_requirements_satisfied", float(alg["satisfied"]),
                "monotone RMSE+uncertainty in SNR"))

    print()
    print("=" * 72)
    print("bench_schedule — paper Table II + Fig. 5 (batch-level scheme)")
    print("=" * 72)
    sch = bench_schedule.run()
    csv.append(("tableII_cpu_speedup", sch["cpu_speedup"],
                "packed+batch-level vs naive, CPU wall"))
    csv.append(("fig5_weight_traffic_reduction", sch["traffic_reduction"],
                "sampling-level / batch-level weight bytes"))
    csv.append(("tableII_modeled_v5e_speedup", sch["modeled_v5e_speedup"],
                "latency model, paper's workload"))

    print()
    print("=" * 72)
    print("bench_latency_model — paper Table I + Fig. 8 (PE sweep / schemes)")
    print("=" * 72)
    lat = bench_latency_model.run()
    base, mid, opt = lat["schemes"]
    csv.append(("tableI_scheme_speedup",
                base["latency_ms"] / opt["latency_ms"],
                "packed+batch-level vs conventional, modeled"))

    print()
    print("=" * 72)
    print("bench_ivim_packed — fused megakernel vs per-op plan vs unpacked")
    print("=" * 72)
    ivp = bench_ivim_packed.run(smoke=args.smoke)
    csv.append(("ivim_packed_plan_speedup", ivp["speedup"],
                "plan-compiled packed serving vs apply_all_samples, wall"))
    csv.append(("ivim_packed_traffic_reduction", ivp["traffic_reduction"],
                "plan traffic: sampling-level / batch-level weight bytes"))
    csv.append(("ivim_fused_vs_per_op_speedup", ivp["fused_vs_per_op"],
                "whole-plan megakernel vs per-op executor, wall"))
    csv.append(("ivim_fused_bytes_reduction", ivp["fused_bytes_reduction"],
                "plan traffic: per-op / fused modeled HBM bytes"))
    csv.append(("ivim_int8_weight_bytes_ratio",
                ivp["quantized"]["weight_bytes_ratio"],
                "int8 / fp32 modeled fused weight bytes (gate <= 0.35)"))
    csv.append(("ivim_int8_max_delta", ivp["quantized"]["max_delta_vs_fp32"],
                "int8 vs fp32 fused moments, max abs"))
    # canonical perf-trajectory artifact (fused vs per-op vs unpacked, with
    # backend + shape provenance) — future PRs compare against this file.
    # Smoke runs must not clobber the committed full-size numbers.
    if args.smoke:
        print(f"[smoke] skipping {bench_ivim_packed.BENCH_JSON} "
              f"(full-size runs only)")
    else:
        bench_ivim_packed.write_bench_json(ivp)
        print(f"wrote {bench_ivim_packed.BENCH_JSON}")

    print()
    print("=" * 72)
    print("bench_kernels — Pallas kernels vs oracles + grid traffic")
    print("=" * 72)
    ker = bench_kernels.run()
    csv.append(("kernel_masked_ffn_max_err", ker["masked_ffn_max_err"],
                "allclose vs jnp oracle"))
    csv.append(("kernel_weight_fetch_reduction",
                ker["weight_fetches_sampling_level"]
                / ker["weight_fetches_batch_level"],
                "BlockSpec revisit counts"))

    print()
    print("=" * 72)
    print("bench_serving — continuous batching vs looped one-shot serving")
    print("=" * 72)
    srv = bench_serving.run(smoke=args.smoke, mixed=True, chaos=True)
    csv.append(("serving_continuous_batching_speedup", srv["speedup"],
                "server tok/s over looped serve_uncertain, Poisson trace"))
    csv.append(("serving_fused_decode_speedup", srv["fused_vs_per_op"],
                "fused single-launch decode vs per-op decode, server tok/s"))
    csv.append(("serving_fused_decode_bytes_reduction",
                srv["modeled_bytes_per_token_perop"]
                / srv["modeled_bytes_per_token_fused"],
                "modeled per-token decode HBM bytes, per-op / fused"))
    csv.append(("serving_uncertainty_max_delta", srv["max_unc_delta"],
                "per-token rel-unc |server - one-shot|"))
    csv.append(("serving_kv_bf16_bytes_reduction",
                srv["quantized"]["modeled_bytes_per_token_kv_f32"]
                / srv["quantized"]["modeled_bytes_per_token_kv_bf16"],
                "modeled decode HBM bytes/token, f32 cache / bf16 cache"))
    if srv["mixed"] is not None:
        csv.append(("serving_mixed_pool_voxels_per_s",
                    srv["mixed"]["voxels_per_s"],
                    "IVIM voxel-chunk throughput interleaved with the LM "
                    "trace in one pool"))
    if srv["chaos"] is not None:
        csv.append(("serving_chaos_requests_lost",
                    float(srv["chaos"]["lost"] + srv["chaos"]["shed"]),
                    "requests lost or shed when a seeded FaultPlan kills "
                    "1 of 3 router hosts mid-run (gate: 0)"))
        csv.append(("serving_chaos_recovery_time_s",
                    srv["chaos"]["recovery_time_s"],
                    "worst host-death -> all victims re-placed window, "
                    "virtual seconds"))
        csv.append(("serving_chaos_retries",
                    float(srv["chaos"]["retries"]),
                    "failover resubmissions exercised by the seeded plan"))
    # canonical serving perf-trajectory artifact (fused vs per-op decode,
    # with backend + shape provenance). Smoke runs must not clobber the
    # committed full-size numbers.
    if args.smoke:
        print(f"[smoke] skipping {bench_serving.BENCH_JSON} "
              f"(full-size runs only)")
    else:
        bench_serving.write_bench_json(srv)
        print(f"wrote {bench_serving.BENCH_JSON}")

    print()
    print("=" * 72)
    print("bench_roofline — dry-run roofline tables (see EXPERIMENTS.md)")
    print("=" * 72)
    bench_roofline.main()

    print()
    print("name,value,derived")
    for name, value, derived in csv:
        print(f"{name},{value:.6g},{derived}")


if __name__ == "__main__":
    sys.exit(main())
