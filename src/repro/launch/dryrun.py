import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract the roofline terms from the compiled artifact.

The lines above MUST stay the first statements in this module — jax
locks the device count at first init, and only the dry-run may see 512
placeholder devices (tests/benches see 1). The dry-run is CPU-only: on a
machine with a TPU it (and every launch/sweep.py child) leaves the chip
to the one process that serves on it.

Per cell this produces:
  * proof of coherence: .lower().compile() succeeds under the 16x16
    single-pod mesh and the (2,16,16) multi-pod mesh,
  * memory_analysis()  — per-device argument/output/temp bytes (fits check),
  * cost_analysis()    — per-device HLO FLOPs and bytes accessed,
  * a collective-traffic table parsed from the post-partitioning HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute, per-device bytes),
  * the three roofline terms (seconds) + dominant bottleneck + the
    MODEL_FLOPS / HLO_FLOPs usefulness ratio.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k \
      [--multi-pod] [--bayesian N] [--out results/...json] [--hlo-dump dir]
"""

import argparse
import json
import re
import sys
import time

import jax
import numpy as np

from repro import compat
from repro.configs import SHAPES, get_config
from repro.configs.cells import skip_reason
from repro.core.latency_model import V5E, roofline_terms
from repro.data import pipeline as data_pipeline
from repro.distributed import sharding
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import OptimizerConfig, build_optimizer
from repro.train import TrainConfig, make_train_step, train_state_specs

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum per-device output bytes of every collective op in the
    post-partitioning HLO. Shapes in the SPMD module are per-device, so the
    totals are per-chip wire bytes (all-reduce is counted once; the
    ring-algorithm 2x factor is folded into the roofline constant)."""
    out = {k: 0 for k in _COLLECTIVES}
    # e.g.:  %all-reduce.5 = bf16[1024,512]{1,0} all-reduce(...)
    pat = re.compile(
        r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    )
    for m in pat.finditer(hlo_text):
        dtype, dims, kind = m.groups()
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] += n * _DTYPE_BYTES[dtype]
    # tuple-result collectives: (bf16[..], bf16[..]) all-reduce(...)
    pat_tuple = re.compile(
        r"=\s+\(([^)]+)\)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    )
    shape_pat = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for m in pat_tuple.finditer(hlo_text):
        shapes, kind = m.groups()
        for sm in shape_pat.finditer(shapes):
            dtype, dims = sm.groups()
            if dtype not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            out[kind] += n * _DTYPE_BYTES[dtype]
    return out


def pick_optimizer(cfg) -> OptimizerConfig:
    """Adafactor above ~40B params (HBM budget: Adam moments at fp32 would
    blow the 16 GB/chip budget for arctic/qwen2-vl-72b — DESIGN §4).
    Adafactor runs without the global-norm clip (its per-tensor RMS update
    clipping bounds steps; saves a full pass over the gradient stacks)."""
    big = cfg.param_count() > 40e9
    if big:
        return OptimizerConfig(name="adafactor", clip_norm=0.0)
    return OptimizerConfig(name="adamw")


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D train (fwd+bwd), 2*N*D prefill, 2*N*B decode;
    N = active params for MoE."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    return float(mult) * n * tokens


def _state_shardings(mesh, state_specs):
    """Sharding tree for the full train state: params rules apply to params,
    optimizer moments (path-mirrored), and EF residuals; scalars replicate."""
    return sharding.param_shardings(mesh, state_specs)


def _sharded_bytes(specs, shardings) -> int:
    """Exact per-device resident bytes of a spec tree under its shardings."""
    total = 0
    for spec, sh in zip(jax.tree.leaves(specs), jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "shard_shape"))):
        shard = sh.shard_shape(spec.shape)
        n = 1
        for d in shard:
            n *= d
        total += n * np.dtype(spec.dtype).itemsize
    return total


def analytic_memory(cfg, shape, mesh, resident_trees) -> dict:
    """TPU-expected per-device memory: exact resident state (params, opt,
    grads, caches — summed from the actual sharding trees) + modeled
    activation terms. The CPU-backend temp measurement is an UPPER bound
    (XLA:CPU hoists bf16->f32 converts of loop-invariant stacks out of
    loops, materializing fp32 copies of gradient/residual stacks that the
    TPU pipeline fuses — verified in the arctic buffer-assignment dump)."""
    chips = mesh.size
    resident = sum(_sharded_bytes(s, sh) for s, sh in resident_trees)
    out = {"resident_state_bytes": int(resident)}
    if shape.kind == "train":
        b, s, d = shape.global_batch, shape.seq_len, cfg.d_model
        # remat residual stack: one [B,S,D] bf16 per layer, sharded over
        # batch x model (seq) as measured in the partitioned HLO
        resid = cfg.n_layers * b * s * d * 2 / chips
        # gradients: bf16, same sharding as the params -> params' byte size
        grads = 2 * cfg.param_count() / chips
        # transient working set: ~3 live layer-sized activation sets
        f_eff = max(cfg.d_ff, d)
        trans = 3 * b * s * (d + f_eff) * 2 / chips
        out["residual_stack_bytes"] = int(resid)
        out["grad_bytes"] = int(grads)
        out["transient_model_bytes"] = int(trans)
        out["analytic_bytes"] = int(resident + resid + grads + trans)
    else:
        b, s, d = shape.global_batch, shape.seq_len, cfg.d_model
        live = shape.kind == "prefill"
        trans = (3 * b * min(s, cfg.attn_chunk) * d * 2 / chips
                 if live else 2 * b * d * 2 / max(1, chips // 16))
        out["transient_model_bytes"] = int(trans)
        out["analytic_bytes"] = int(resident + trans)
    out["fits_16gb_analytic"] = bool(out["analytic_bytes"] < 16e9)
    return out


def _cell_config(arch: str, bayesian: int, overrides: dict | None):
    over = dict(overrides or {})
    if bayesian:
        over.update(mask_samples=bayesian)
    return get_config(arch, **over)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               bayesian: int = 0, overrides: dict | None = None,
               shape_override=None):
    """Build + lower one cell. Returns (lowered, meta dict)."""
    import dataclasses as _dc
    shape = shape_override if shape_override is not None \
        else SHAPES[shape_name]
    cfg = _cell_config(arch, bayesian, overrides)
    if bayesian and shape.kind != "train":
        # Bayesian serving: every request is evaluated under all N masks,
        # so the served batch is N x the request batch (rows grouped
        # sample-major, as serving.serve_uncertain arranges them)
        shape = _dc.replace(shape, global_batch=shape.global_batch * bayesian)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    compat.set_mesh(mesh)

    if shape.kind == "train":
        opt_cfg = pick_optimizer(cfg)
        optimizer = build_optimizer(opt_cfg)
        tcfg = TrainConfig(grad_accum=1, compress_grads=multi_pod)
        step = make_train_step(model, optimizer, tcfg)
        state_specs = train_state_specs(model, optimizer,
                                        compress=tcfg.compress_grads)
        state_sh = _state_shardings(mesh, state_specs)
        batch_specs = model.input_specs(shape)["batch"]
        batch_sh = sharding.batch_shardings(mesh, batch_specs)
        lowered = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state_specs, batch_specs)
        return lowered, {"kind": "train", "optimizer": opt_cfg.name,
                         "cfg": cfg, "shape": shape, "mesh": mesh,
                         "resident": [(state_specs, state_sh)]}

    params_specs = model.param_specs()
    params_sh = sharding.param_shardings(mesh, params_specs)

    if shape.kind == "prefill":
        batch_specs = model.input_specs(shape)["batch"]
        batch_sh = sharding.batch_shardings(mesh, batch_specs)
        cache_sp = model.cache_specs(shape.global_batch, shape.seq_len)
        cache_sh = sharding.cache_shardings(mesh, cache_sp)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, max_seq=shape.seq_len)

        with mesh:
            lowered = jax.jit(
                prefill_fn,
                in_shardings=(params_sh, batch_sh),
                out_shardings=(None, cache_sh),
            ).lower(params_specs, batch_specs)
        return lowered, {"kind": "prefill", "cfg": cfg, "shape": shape,
                         "mesh": mesh,
                         "resident": [(params_specs, params_sh),
                                      (cache_sp, cache_sh)]}

    # decode: one new token against a seq_len-deep cache
    ins = model.input_specs(shape)
    cache_sp = model.cache_specs(shape.global_batch, shape.seq_len)
    cache_sh = sharding.cache_shardings(mesh, cache_sp)
    tok_sh = sharding.batch_shardings(mesh, {"tokens": ins["tokens"]})

    def decode_fn(params, caches, tokens, pos):
        return model.decode_step(params, caches, tokens, pos)

    with mesh:
        lowered = jax.jit(
            decode_fn,
            in_shardings=(params_sh, cache_sh, tok_sh["tokens"], None),
            out_shardings=(None, cache_sh),
            donate_argnums=(1,),
        ).lower(params_specs, cache_sp, ins["tokens"], ins["pos"])
    return lowered, {"kind": "decode", "cfg": cfg, "shape": shape,
                     "mesh": mesh,
                     "resident": [(params_specs, params_sh),
                                  (cache_sp, cache_sh)]}


def _compiled_costs(lowered) -> dict:
    """flops / bytes / collectives of one compiled probe."""
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll}


PROBE_SEQS = (128, 256, 512)


def _probe_seqs(cfg, shape) -> tuple[int, ...]:
    """Probe sequence lengths per family, chosen so the probe exercises the
    SAME attention/mixing path as the full cell with all loops unrolled:
      * ssm: multiples of the mLSTM chunk (1/2/3 chunks — exactly linear),
      * hybrid beyond the local window: 2w/3w/4w (banded attention is
        linear in S there; the quadratic term fits ~0),
      * default: short enough for the un-chunked attention path (S^2 fits
        the quadratic exactly).
    """
    if cfg.family == "ssm":
        c = cfg.chunk_size
        return (c, 2 * c, 3 * c)
    if shape.kind == "decode":
        # decode cost is linear in cache length; no sequence loops involved
        return PROBE_SEQS
    if cfg.local_window and shape.seq_len > cfg.local_window:
        w = cfg.local_window
        return (2 * w, 3 * w, 4 * w)
    if cfg.causal and shape.seq_len > cfg.attn_chunk:
        # exercise the REAL chunked-attention path (unrolled): GSPMD picks
        # scale-dependent collective strategies, so probes must present the
        # same per-chunk shapes the full cell uses
        c = cfg.attn_chunk
        return (2 * c, 3 * c, 4 * c)
    return PROBE_SEQS


def _quad_fit_eval(svals, yvals, s_target: float) -> float:
    """Exact quadratic through 3 (s, y) points, evaluated at s_target.
    Costs are polynomial (<=2) in sequence length: attention is S^2, token
    work is S, setup is constant — so the fit *extrapolates exactly* up to
    compiler fusion jitter; clamped below by the largest observation."""
    (s1, s2, s3), (y1, y2, y3) = svals, yvals
    d = (s1 - s2) * (s1 - s3) * (s2 - s3)
    a = (s3 * (y2 - y1) + s2 * (y1 - y3) + s1 * (y3 - y2)) / d
    b = (s3 * s3 * (y1 - y2) + s2 * s2 * (y3 - y1)
         + s1 * s1 * (y2 - y3)) / d
    c = y1 - a * s1 * s1 - b * s1
    return max(float(max(yvals)), a * s_target ** 2 + b * s_target + c)


def _slstm_step_cost(cfg, batch: int, n_chips: int) -> dict:
    """Analytic per-timestep cost of one sLSTM cell (per device).

    The sequential sLSTM scan cannot be unrolled for analysis (S copies of
    the cell blow up compile time), so its in-scan body — which HLO cost
    analysis counts exactly ONCE — is added back analytically:
      recurrent block-diag matmul: 2 * B * (D/H) * 4D flops,
      gate/state elementwise (~12 f32 ops over [B, D]),
      state traffic: c/n/h/m read+write f32 + the step's preactivation.
    """
    batch_shards = max(1, n_chips // 16)     # data (x pod) axes; model = 16
    b_dev = batch / batch_shards
    d, h = cfg.d_model, cfg.n_heads
    flops = 8 * b_dev * d * d / h + 12 * b_dev * d
    # 4 f32 states read+write + 4D preactivation read + h output write
    bytes_ = (8 + 4 + 1) * b_dev * d * 4
    return {"flops": flops, "bytes": bytes_}


def probe_costs(arch: str, shape_name: str, *, multi_pod: bool,
                bayesian: int = 0, overrides: dict | None = None) -> dict:
    """Loop-corrected per-device costs via (depth x sequence) probes.

    XLA's cost_analysis (and the HLO text) count every ``while`` body ONCE
    regardless of trip count — this hides both the layer scan AND the
    sequence loops (attention q-chunk scan, xLSTM chunk/step scans).
    Correction: compile small probe variants that contain NO loops at all —
    segments unrolled at 1 and 2 repetitions, sequence lengths in
    PROBE_SEQS (short enough that attention takes its full, un-chunked
    path; xLSTM scans unroll via cfg.analysis_unroll) — then solve

        cost(L, S) = outside(S) + sum_i reps_i * body_i(S)

    per metric, where outside/body are quadratic polynomials in S (exact:
    attention is S^2, everything else linear), and evaluate at the cell's
    true depth and sequence length.
    """
    import dataclasses as _dc
    shape = SHAPES[shape_name]
    s_target = shape.seq_len
    cfg = _cell_config(arch, bayesian, overrides)
    segs = cfg.segments()
    base_spec = tuple((tuple(s.pattern), 1) for s in segs)

    probe_seqs = _probe_seqs(cfg, shape)

    def probe(spec, seq):
        over = dict(overrides or {})
        over.update(segments_override=spec, scan_layers=False,
                    analysis_unroll=True)
        lowered, _ = lower_cell(arch, f"__probe_{seq}", multi_pod=multi_pod,
                                bayesian=bayesian, overrides=over,
                                shape_override=_dc.replace(shape,
                                                           seq_len=seq))
        return _compiled_costs(lowered)

    metrics = ("flops", "bytes") + _COLLECTIVES

    def get(c, m):
        return c["coll"][m] if m in _COLLECTIVES else c[m]

    # per-seq-length: solve the depth system at each S, then fit in S
    outside_by_s: list[dict] = []
    bodies_by_s: list[list[dict]] = []
    for seq in probe_seqs:
        c_a = probe(base_spec, seq)
        bodies = []
        for i in range(len(segs)):
            spec = tuple((p, 2 if j == i else 1)
                         for j, (p, _) in enumerate(base_spec))
            c_b = probe(spec, seq)
            bodies.append({m: max(0.0, get(c_b, m) - get(c_a, m))
                           for m in metrics})
        outside_by_s.append(
            {m: max(0.0, get(c_a, m) - sum(b[m] for b in bodies))
             for m in metrics})
        bodies_by_s.append(bodies)

    def fit(series):  # series: one value per probe_seqs entry
        return _quad_fit_eval(probe_seqs, series, s_target)

    outside = {m: fit([o[m] for o in outside_by_s]) for m in metrics}
    body_fits = [
        {m: fit([bodies_by_s[k][i][m] for k in range(len(probe_seqs))])
         for m in metrics}
        for i in range(len(segs))
    ]
    # analytic correction: sequential sLSTM cells are counted once by the
    # HLO analysis; add the remaining (S_target - 1) steps
    n_chips = 512 if multi_pod else 256
    step = _slstm_step_cost(cfg, shape.global_batch, n_chips)
    for seg, b in zip(segs, body_fits):
        n_slstm = sum(k == "slstm" for k in seg.pattern)
        if n_slstm:
            b["flops"] += n_slstm * (s_target - 1) * step["flops"]
            b["bytes"] += n_slstm * (s_target - 1) * step["bytes"]
    total_m = {m: outside[m] + sum(s.reps * b[m]
                                   for s, b in zip(segs, body_fits))
               for m in metrics}
    total = {"flops": total_m["flops"], "bytes": total_m["bytes"],
             "coll": {k: int(total_m[k]) for k in _COLLECTIVES}}
    return {"total": total,
            "outside": {"flops": outside["flops"], "bytes": outside["bytes"],
                        "coll": {k: int(outside[k]) for k in _COLLECTIVES}},
            "per_segment_body": [
                {"flops": b["flops"], "bytes": b["bytes"],
                 "coll": {k: int(b[k]) for k in _COLLECTIVES}}
                for b in body_fits],
            "segment_reps": [s.reps for s in segs],
            "probe_seqs": list(probe_seqs)}


def analyze(lowered, meta, *, hlo_dump: str | None = None,
            probes: dict | None = None) -> dict:
    cfg, shape, mesh = meta["cfg"], meta["shape"], meta["mesh"]
    n_chips = mesh.size
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    result: dict = {
        "env": compat.version_summary(),
        "arch": cfg.arch_id, "shape": shape.name, "kind": meta["kind"],
        "mesh": dict(zip(mesh.axis_names,
                         (mesh.shape[a] for a in mesh.axis_names))),
        "n_chips": n_chips,
        "compile_s": round(compile_s, 1),
    }

    try:
        ma = compiled.memory_analysis()
        result["memory"] = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
        live = (result["memory"]["argument_bytes"]
                + result["memory"]["output_bytes"]
                + result["memory"]["temp_bytes"]
                - result["memory"]["alias_bytes"])
        result["memory"]["est_live_bytes_per_device"] = int(live)
        result["memory"]["fits_16gb_hbm"] = bool(live < 16e9)
    except Exception as e:  # noqa: BLE001 — record, don't fail the cell
        result["memory"] = {"error": str(e)}
    try:
        result["memory_analytic"] = analytic_memory(
            cfg, shape, mesh, meta.get("resident", []))
    except Exception as e:  # noqa: BLE001
        result["memory_analytic"] = {"error": str(e)}

    try:
        ca = compiled.cost_analysis()
        flops = float(ca.get("flops", 0.0))
        bytes_accessed = float(ca.get("bytes accessed", 0.0))
        result["cost"] = {"hlo_flops_per_device": flops,
                          "hlo_bytes_per_device": bytes_accessed}
    except Exception as e:  # noqa: BLE001
        flops = bytes_accessed = 0.0
        result["cost"] = {"error": str(e)}

    hlo = compiled.as_text()
    if hlo_dump:
        with open(hlo_dump, "w") as f:
            f.write(hlo)
    coll = collective_bytes(hlo)
    result["collectives_raw_scan_body_once"] = coll

    if probes is not None:
        # trip-count-corrected numbers from the unrolled probes
        flops = probes["total"]["flops"]
        bytes_accessed = probes["total"]["bytes"]
        coll = probes["total"]["coll"]
        result["cost"] = {"hlo_flops_per_device": flops,
                          "hlo_bytes_per_device": bytes_accessed,
                          "source": "probe-extrapolated"}
        result["probe"] = {
            "outside": probes["outside"],
            "per_segment_body": probes["per_segment_body"],
            "segment_reps": probes["segment_reps"],
        }
    result["collectives"] = coll
    coll_total = sum(coll.values())

    terms = roofline_terms(flops, bytes_accessed, coll_total, V5E)
    mf = model_flops(cfg, shape)
    result["roofline"] = {
        "compute_s": terms.compute_s,
        "memory_s": terms.memory_s,
        "collective_s": terms.collective_s,
        "dominant": terms.dominant,
        "bound_s": terms.bound_s,
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips / flops) if flops else None,
        # roofline fraction: useful model FLOPs per device over the time the
        # dominant term implies, vs chip peak
        "roofline_fraction": ((mf / n_chips) / terms.bound_s
                              / V5E.peak_flops_bf16) if terms.bound_s else None,
    }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--bayesian", type=int, default=0,
                    help="enable Masksembles with N samples")
    ap.add_argument("--out", default="")
    ap.add_argument("--hlo-dump", default="")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides key=value (int/float/str)")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the trip-count probe compiles")
    args = ap.parse_args(argv)

    reason = skip_reason(args.arch, SHAPES[args.shape])
    if reason:
        result = {"arch": args.arch, "shape": args.shape, "skipped": reason}
        print(json.dumps(result, indent=2))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(result, f, indent=2)
        return 0

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        if v in ("True", "False"):
            v = v == "True"
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        overrides[k] = v

    t0 = time.time()
    lowered, meta = lower_cell(args.arch, args.shape,
                               multi_pod=args.multi_pod,
                               bayesian=args.bayesian, overrides=overrides)
    lower_s = time.time() - t0
    probes = None
    if not args.no_probes:
        try:
            probes = probe_costs(args.arch, args.shape,
                                 multi_pod=args.multi_pod,
                                 bayesian=args.bayesian,
                                 overrides=overrides)
        except Exception as e:  # noqa: BLE001 — keep the fit proof alive
            probes = None
            print(f"probe extrapolation failed: {e}", file=sys.stderr)
    result = analyze(lowered, meta, hlo_dump=args.hlo_dump or None,
                     probes=probes)
    result["lower_s"] = round(lower_s, 1)
    if args.bayesian:
        result["bayesian_samples"] = args.bayesian
    print(json.dumps(result, indent=2))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
