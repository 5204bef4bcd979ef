"""Packed-plan IVIM serving: fused megakernel vs per-op plan vs unpacked.

The paper's clinical workload: every voxel of a diffusion-MRI volume is
evaluated under all N masks and reduced to predictive moments. Three tiers:

  * **unpacked** — ``ivim.model.apply_all_samples`` (mask-as-multiply,
    sampling expansion) + ``uncertainty.predictive_moments``;
  * **per-op**   — the compiled :class:`repro.core.plan.PackedPlan` served
    through ``serving.engine.predict_packed(fused=False)``: one
    kernels/masked_ffn launch per PackedPair, moments outside;
  * **fused**    — ``predict_packed(fused=True)``: the whole op chain in ONE
    kernels/fused_plan launch with the in-kernel Welford moments epilogue —
    the ``[N, B, 4]`` sample tensor is never materialized.

Reports measured wall-clock + voxel rate per tier, the plan's own analytic
traffic (per-op batch-level vs sampling-level vs fused bytes) and modeled
v5e latency, all priced from op metadata, and guards fused-vs-per-op
equivalence (exits nonzero past fp32 tolerance — the CI smoke leg relies on
this). ``write_bench_json`` emits the canonical BENCH_plan.json perf-
trajectory artifact (benchmarks/run.py calls it).

    PYTHONPATH=src python -m benchmarks.bench_ivim_packed \
        [--smoke] [--fused] [--json [PATH]]

``--fused`` serves the packed tiers through the process kernel-backend
probe instead of forcing the pure-XLA ref off-TPU — run it under
``REPRO_KERNEL_BACKEND=pallas-interpret`` to exercise the actual fused
kernel (the CI smoke leg).
"""

from __future__ import annotations

import argparse
import json
import pathlib

import jax
import jax.numpy as jnp

from benchmarks.bench_schedule import _timeit
from repro import compat
from repro.core import scheduler
from repro.core import uncertainty as unc_lib
from repro.ivim import data as ivim_data
from repro.ivim import model as ivim_model
from repro.serving import engine

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_plan.json"


def run(n_voxels: int = 20_000, n_masks: int = 8, scale: float = 2.0,
        smoke: bool = False, quiet: bool = False,
        probe_backend: bool = False) -> dict:
    if smoke:
        n_voxels, n_masks = 512, 4
    cfg = ivim_model.IvimConfig(n_masks=n_masks, scale=scale)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    ds = ivim_data.make_dataset(ivim_data.SyntheticConfig(
        n_voxels=n_voxels, snr=20.0, seed=0))
    x = ds["signals"]

    # unpacked baseline: mask-as-multiply, batch expanded x N, moments after
    def unpacked(xb):
        return unc_lib.predictive_moments(
            ivim_model.apply_all_samples(cfg, params, state, xb))

    # compiled plan, served through the engine. Off-TPU the xla tier keeps
    # the wall-clock honest (the Pallas interpreter is an emulator);
    # probe_backend=True defers to the process probe so CI can exercise the
    # real fused kernel under REPRO_KERNEL_BACKEND=pallas-interpret.
    plan = ivim_model.pack_for_serving(cfg, params, state)
    backend = None if (compat.on_tpu() or probe_backend) else "xla"

    def packed_per_op(xb):
        return engine.predict_packed(plan, xb, backend=backend, fused=False)

    def packed_fused(xb):
        return engine.predict_packed(plan, xb, backend=backend, fused=True)

    t_unpacked = _timeit(jax.jit(unpacked), x)
    t_per_op = _timeit(jax.jit(packed_per_op), x)
    t_fused = _timeit(jax.jit(packed_fused), x)

    # equivalence guard: the smoke legs rely on the nonzero exit
    m_o, s_o = packed_per_op(x)
    m_f, s_f = packed_fused(x)
    max_delta = float(max(jnp.abs(m_f - m_o).max(), jnp.abs(s_f - s_o).max()))
    if max_delta > 1e-3:
        raise SystemExit(f"fused vs per-op moments diverge: {max_delta:.3e}")

    # quantized serving: the SAME plan re-lowered at int8 weight precision
    # (per-output-channel scales + bf16 biases, quantized once at lowering).
    # Gates (the CI quantized leg relies on the nonzero exits): moments
    # within int8 tolerance of the fp32 plan, and modeled fused weight
    # bytes <= 0.35x fp32 at the f32 master-param width.
    from repro.core import plan as plan_lib
    plan_q = plan.with_precision(plan_lib.Precision(weights="int8"))

    def packed_quant(xb):
        return engine.predict_packed(plan_q, xb, backend=backend, fused=True)

    t_quant = _timeit(jax.jit(packed_quant), x)
    m_q, s_q = packed_quant(x)
    quant_delta = float(max(jnp.abs(m_q - m_f).max(),
                            jnp.abs(s_q - s_f).max()))
    if quant_delta > 1e-2:
        raise SystemExit(f"int8 vs fp32 moments diverge: {quant_delta:.3e}")
    tm_fused_f32 = plan.traffic(n_voxels, 4, fused=True, moments=True)
    tm_fused_q = plan_q.traffic(n_voxels, 4, fused=True, moments=True)
    quant_ratio = tm_fused_q.weight_bytes / tm_fused_f32.weight_bytes
    if quant_ratio > 0.35:
        raise SystemExit(f"int8 fused weight bytes {quant_ratio:.4f}x fp32 "
                         f"(acceptance gate: <= 0.35x)")

    tm_batch = plan.traffic(n_voxels)
    tm_samp = plan.traffic(n_voxels,
                           schedule=scheduler.Schedule("sampling", chunk=64))
    tm_fused = plan.traffic(n_voxels, fused=True, moments=True)
    lat_opt = plan.modeled_latency(n_voxels)
    lat_fused = plan.modeled_latency(n_voxels, fused=True)
    lat_base = plan.modeled_latency(n_voxels, packed=False, batch_level=False)

    out = {
        "n_voxels": n_voxels,
        "n_masks": n_masks,
        "width": cfg.width,
        "keep": int(plan.pairs[0].keep),
        "sample_axis": plan.sample_axis,
        "backend": backend or compat.kernel_backend(),
        "wall_unpacked_ms": t_unpacked * 1e3,
        "wall_packed_ms": t_per_op * 1e3,
        "wall_fused_ms": t_fused * 1e3,
        "voxel_rate_unpacked": n_voxels / t_unpacked,
        "voxel_rate_packed": n_voxels / t_per_op,
        "voxel_rate_fused": n_voxels / t_fused,
        "speedup": t_unpacked / t_per_op,
        "fused_speedup": t_unpacked / t_fused,
        "fused_vs_per_op": t_per_op / t_fused,
        "fused_max_delta": max_delta,
        "weight_bytes_batch": tm_batch.weight_bytes,
        "weight_bytes_sampling": tm_samp.weight_bytes,
        "traffic_reduction": tm_samp.weight_bytes / max(1,
                                                        tm_batch.weight_bytes),
        "bytes_per_op": tm_batch.total_bytes,
        "bytes_fused": tm_fused.total_bytes,
        "fused_bytes_reduction": tm_batch.total_bytes / max(
            1, tm_fused.total_bytes),
        "modeled_v5e_speedup": lat_base / lat_opt,
        "modeled_v5e_fused_speedup": lat_base / lat_fused,
        "quantized": {
            "wall_fused_int8_ms": t_quant * 1e3,
            "voxel_rate_fused_int8": n_voxels / t_quant,
            "max_delta_vs_fp32": quant_delta,
            "weight_bytes_fused_fp32": tm_fused_f32.weight_bytes,
            "weight_bytes_fused_int8": tm_fused_q.weight_bytes,
            "weight_bytes_ratio": quant_ratio,
        },
    }
    if not quiet:
        print(f"# IVIM volume serving (voxels={n_voxels}, N={n_masks}, "
              f"Nb={cfg.width}, keep={out['keep']}, backend="
              f"{out['backend']})")
        print(f"wall: unpacked {out['wall_unpacked_ms']:.2f} ms -> per-op "
              f"plan {out['wall_packed_ms']:.2f} ms ({out['speedup']:.2f}x) "
              f"-> fused megakernel {out['wall_fused_ms']:.2f} ms "
              f"({out['fused_speedup']:.2f}x, {out['fused_vs_per_op']:.2f}x "
              f"over per-op; max|err| {max_delta:.1e})")
        print(f"plan traffic: {tm_samp.weight_bytes / 1e6:.2f} MB weights "
              f"(sampling-level) -> {tm_batch.weight_bytes / 1e6:.2f} MB "
              f"(batch-level), {out['traffic_reduction']:.1f}x fewer bytes")
        print(f"fused traffic: {tm_batch.total_bytes / 1e6:.2f} MB total "
              f"(per-op) -> {tm_fused.total_bytes / 1e6:.2f} MB (one launch, "
              f"in-kernel moments), {out['fused_bytes_reduction']:.1f}x")
        print(f"modeled v5e: {lat_base * 1e6:.1f} us -> per-op "
              f"{lat_opt * 1e6:.1f} us ({out['modeled_v5e_speedup']:.2f}x) "
              f"-> fused {lat_fused * 1e6:.1f} us "
              f"({out['modeled_v5e_fused_speedup']:.2f}x)")
        q = out["quantized"]
        print(f"quantized: int8 fused {q['wall_fused_int8_ms']:.2f} ms, "
              f"weight bytes {q['weight_bytes_fused_int8'] / 1e3:.1f} kB vs "
              f"fp32 {q['weight_bytes_fused_fp32'] / 1e3:.1f} kB "
              f"({q['weight_bytes_ratio']:.3f}x, gate <= 0.35), "
              f"max|err| vs fp32 {q['max_delta_vs_fp32']:.1e}")
    return out


def write_bench_json(out: dict, path: pathlib.Path = BENCH_JSON) -> dict:
    """Emit the canonical BENCH_plan.json perf-trajectory artifact: fused vs
    per-op vs unpacked rates and modeled bytes, stamped with backend + shape
    provenance so future PRs compare like with like."""
    from repro.obs import export as obs_export
    from repro.obs import registry as obs_registry
    payload = {
        "bench": "bench_ivim_packed",
        "provenance": {
            **compat.version_summary(),
            **obs_export.host_provenance(),
            "serving_backend": out["backend"],
            "n_voxels": out["n_voxels"],
            "n_masks": out["n_masks"],
            "width": out["width"],
            "keep": out["keep"],
            "sample_axis": out["sample_axis"],
        },
        "registry_snapshot": obs_registry.REGISTRY.snapshot(),
        "wall_ms": {
            "unpacked": out["wall_unpacked_ms"],
            "packed_per_op": out["wall_packed_ms"],
            "packed_fused": out["wall_fused_ms"],
        },
        "voxel_rate_per_s": {
            "unpacked": out["voxel_rate_unpacked"],
            "packed_per_op": out["voxel_rate_packed"],
            "packed_fused": out["voxel_rate_fused"],
        },
        "speedup": {
            "per_op_vs_unpacked": out["speedup"],
            "fused_vs_unpacked": out["fused_speedup"],
            "fused_vs_per_op": out["fused_vs_per_op"],
        },
        "modeled_hbm_bytes": {
            "per_op": out["bytes_per_op"],
            "fused": out["bytes_fused"],
            "reduction": out["fused_bytes_reduction"],
        },
        "equivalence_max_delta": out["fused_max_delta"],
        "quantized": out["quantized"],
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")
    return payload


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized volume")
    ap.add_argument("--fused", action="store_true",
                    help="serve through the process kernel-backend probe "
                         "(exercises the fused Pallas kernel under "
                         "REPRO_KERNEL_BACKEND=pallas-interpret)")
    ap.add_argument("--json", nargs="?", const=str(BENCH_JSON), default=None,
                    metavar="PATH", help="write the canonical "
                    "BENCH_plan.json artifact")
    args = ap.parse_args(argv)
    out = run(smoke=args.smoke, probe_backend=args.fused)
    if args.json:
        write_bench_json(out, pathlib.Path(args.json))


if __name__ == "__main__":
    main()
