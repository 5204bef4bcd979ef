"""Continuous batching vs looped one-shot serving on a Poisson trace.

    PYTHONPATH=src python -m benchmarks.bench_serving [--smoke] [--fused]
                                                      [--mixed] [--seed S]
                                                      [--trace-out F]
                                                      [--metrics-out F]

Replays one Poisson arrival trace through two serving paths at matched
uncertainty output (same N-mask posterior per token):

  * **looped one-shot** — requests processed strictly in arrival order, one
    ``serve_uncertain`` call (batch 1) per request: the pre-server behaviour,
    where the batch-level mask schedule never amortizes across requests;
  * **continuous batching** — the same requests through
    :class:`repro.serving.server.BayesianLMServer`: arrivals prefill into
    free slots while resident requests keep decoding, so every jitted decode
    step serves up to ``max_slots`` requests.

The continuous-batching leg runs TWICE — once with the fused single-launch
decode step (``core.plan.compile_decode_step``: KV gather, attention over
the slot pool, the Bayesian FFN and the Welford posterior in one
``kernels/fused_plan`` launch) and once with the per-op decode path — and
reports tok/s, p50/p99 request latency and the modeled per-token HBM bytes
of each decode executor. ``--fused`` gates on the fused leg: it must
actually run fused (no silent fallback) and must emit tokens bitwise
identical to the per-op decode.

Arrivals are indexed in *decode steps* (a Poisson process sampled at step
granularity) so the trace is hardware-independent and reproducible; the
whole trace is a pure function of ``--seed`` (recorded in the JSON
provenance). Wall time is measured for throughput. Correctness gate:
per-request tokens must match exactly between the paths and per-token
uncertainties to fp32 tolerance — the speedup is scheduling + launch
fusion, not approximation.

``--mixed`` adds the mixed-modality leg: synthetic IVIM scans are submitted
into the SAME server pool (``submit_scan`` voxel-chunk work items)
interleaved with the LM trace. Gates: the pooled scan moments must be
bitwise-identical to the direct ``engine.predict_volume`` path, and the LM
tokens must be unchanged by the co-resident scans.

``--chaos`` adds the fault-tolerance leg: the same LM trace through a
3-host :class:`repro.serving.router.ServingRouter` on a virtual clock,
twice — once unfaulted, once under a seeded
:class:`repro.serving.faults.FaultPlan` replay that kills a host mid-run
(plus scripted drops/delays). Gates: zero requests lost or shed, at least
one host death with at least one retry actually exercised, and every
recovered request's tokens bitwise-identical to both the unfaulted router
run and the single-host server leg. Recovery time (steps from death to
every victim re-placed) and the retry/spill/remesh counts land in the
JSON artifact; ``--chaos-trace-out`` exports the faulted run's span log
for ``verify_obs.py``'s failover lifecycle checks.

Every run also replays the trace once with span tracing enabled
(``ServerConfig(trace=True)``) and gates on the observability overhead
bounds: tokens (and scan moments, when mixed) bitwise-identical to the
untraced replay, and zero added jit retraces (``retrace_total`` must not
move). ``--trace-out`` exports that replay's event log as JSONL —
``benchmarks/verify_obs.py`` replays it into a per-request lifecycle state
machine — and ``--metrics-out`` writes the Prometheus text exposition.
The JSON artifact gains the full registry snapshot.

Full (non-smoke) runs via ``benchmarks/run.py`` emit the canonical
``BENCH_serving.json`` perf-trajectory artifact.
"""

from __future__ import annotations

import argparse
import pathlib
import time

import numpy as np

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_serving.json"


def make_trace(n_requests: int, mean_gap_steps: float, prompt_len: int,
               vocab: int, seed: int = 0):
    """Poisson arrivals (exponential inter-arrival gaps, in decode-step
    units) + random prompts. Returns (arrival_steps [R], prompts [R, P])."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(mean_gap_steps, n_requests)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    prompts = rng.integers(0, vocab, (n_requests, prompt_len))
    return arrivals, prompts


def _run_baseline(model, params, prompts, max_new: int):
    """Looped one-shot: serve_uncertain per request, arrival order."""
    from repro.serving import ServeConfig, serve_uncertain

    cfg = ServeConfig(max_new_tokens=max_new)
    outs = []
    t0 = time.perf_counter()
    for p in prompts:
        gen, unc, _ = serve_uncertain(model, params, p[None], cfg)
        outs.append((np.asarray(gen[0, len(p):]), np.asarray(unc[0])))
    wall = time.perf_counter() - t0
    return outs, wall


def _run_server(model, params, scfg, arrivals, prompts, max_new: int):
    """Replay the trace: submit each request at its arrival step."""
    from repro.serving import BayesianLMServer

    server = BayesianLMServer(model, params, scfg)
    rids: list[int] = []
    pending = list(zip(arrivals, prompts))
    step_i = 0
    t0 = time.perf_counter()
    while pending or server.queue_depth or server.occupied_slots:
        while pending and pending[0][0] <= step_i:
            rids.append(server.submit(pending.pop(0)[1],
                                      max_new_tokens=max_new))
        server.step()
        step_i += 1
    wall = time.perf_counter() - t0
    outs = [(np.asarray(server.result(r).generated, np.int64),
             np.asarray(server.result(r).uncertainty))
            for r in rids]
    return outs, wall, server.metrics.summary()


def _run_mixed(model, params, scfg, arrivals, prompts, max_new: int,
               smoke: bool, seed: int):
    """Replay the LM trace with synthetic IVIM scans interleaved into the
    same pool: scans arrive as voxel-chunk work items (``submit_scan``) at
    step 0 and mid-trace. Returns (lm_outs, scan results, wall, summary)
    where each scan result is (pooled (mean, std), direct (mean, std))."""
    import dataclasses

    import jax

    from repro.ivim import model as ivim_model
    from repro.serving import BayesianLMServer, engine

    icfg = ivim_model.IvimConfig(n_masks=model.cfg.mask_samples, scale=2.0)
    iparams, istate = ivim_model.init(icfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(icfg, iparams, istate)
    n_scans = 1 if smoke else 2
    n_vox = 96 if smoke else 4096
    chunk = 32 if smoke else 512
    rng = np.random.default_rng(seed + 1)
    vols = [rng.uniform(size=(n_vox, icfg.width)).astype(np.float32)
            for _ in range(n_scans)]
    scan_arrivals = [0, int(arrivals[len(arrivals) // 2])][:n_scans]
    # the reference moments, computed OUTSIDE the timed replay
    direct = [engine.predict_packed(plan, v, chunk=chunk) for v in vols]

    scfg = dataclasses.replace(scfg, max_queue=scfg.max_queue + n_scans)
    server = BayesianLMServer(model, params, scfg)
    pending = list(zip(arrivals, prompts))
    scan_pending = list(zip(scan_arrivals, vols))
    rids, sids = [], []
    step_i = 0
    t0 = time.perf_counter()
    while pending or scan_pending or server.queue_depth \
            or server.occupied_slots:
        while pending and pending[0][0] <= step_i:
            rids.append(server.submit(pending.pop(0)[1],
                                      max_new_tokens=max_new))
        while scan_pending and scan_pending[0][0] <= step_i:
            sids.append(server.submit_scan(plan, scan_pending.pop(0)[1],
                                           chunk=chunk))
        server.step()
        step_i += 1
    wall = time.perf_counter() - t0
    lm_outs = [(np.asarray(server.result(r).generated, np.int64),
                np.asarray(server.result(r).uncertainty)) for r in rids]
    scans = [(server.result(s).scan_moments(), d)
             for s, d in zip(sids, direct)]
    return lm_outs, scans, wall, server.metrics.summary()


def _run_router(model, params, scfg, rcfg, arrivals, prompts, max_new: int,
                faults=None):
    """Replay the trace through the multi-host router on a virtual clock
    (1 virtual second per router step — heartbeat timeouts and backoffs
    elapse deterministically, independent of host speed)."""
    from repro.obs.trace import ManualClock
    from repro.serving import ServingRouter

    clock = ManualClock()
    router = ServingRouter(model, params, scfg, rcfg, faults=faults,
                           clock=clock)
    rids: list[int] = []
    pending = list(zip(arrivals, prompts))
    t0 = time.perf_counter()
    while pending or any(not r.done for r in router.records.values()):
        while pending and pending[0][0] <= router.step_i:
            rids.append(router.submit(pending.pop(0)[1],
                                      max_new_tokens=max_new))
        router.step()
        clock.advance(1.0)
        if router.step_i > 10_000:
            raise RuntimeError("router replay did not converge")
    wall = time.perf_counter() - t0
    outs = [(np.asarray(router.result(r).generated, np.int64),
             np.asarray(router.result(r).uncertainty)) for r in rids]
    return outs, wall, router


def _run_chaos(model, params, scfg, arrivals, prompts, max_new: int,
               seed: int, server_outs, trace_out: str | None = None):
    """The fault-tolerance leg: unfaulted 3-host router reference, then
    the same trace under a seeded FaultPlan (host killed mid-run, plus
    scripted drops/delays), traced for verify_obs. Returns the chaos
    result block for the JSON artifact."""
    from repro.obs import trace as obs_trace
    from repro.serving import FaultPlan, RouterConfig

    rcfg = RouterConfig(n_hosts=3, heartbeat_timeout_s=2.5, max_retries=4)
    ref_outs, _, ref_router = _run_router(model, params, scfg, rcfg,
                                          arrivals, prompts, max_new)
    # scope the scripted faults to the steps the run actually occupies —
    # the seeded kill lands in the middle half, while work is in flight
    horizon = max(4, ref_router.step_i)
    faults = FaultPlan.seeded(seed, n_hosts=rcfg.n_hosts, horizon=horizon)

    tracer = obs_trace.TRACER
    tracer.clear()
    tracer.enable()
    try:
        outs, _, router = _run_router(model, params, scfg, rcfg, arrivals,
                                      prompts, max_new, faults=faults)
    finally:
        tracer.disable()
    trace_records = len(tracer.events())
    if trace_out:
        tracer.export_jsonl(trace_out)
    s = router.summary()
    return {
        "n_hosts": rcfg.n_hosts,
        "seed": seed,
        "horizon": horizon,
        "killed_hosts": sorted({e.host for e in faults.events
                                if e.action == "kill"}),
        "kill_steps": sorted(e.step for e in faults.events
                             if e.action == "kill"),
        "requests": s.requests,
        "completed": s.completed,
        "lost": s.lost,
        "shed": s.shed,
        "host_deaths": s.host_deaths,
        "retries": s.retries,
        "spills": s.spills,
        "remeshes": s.remeshes,
        "steps": s.steps,
        "recovery_steps": list(s.recovery_steps),
        # virtual clock: 1 s per router step, so worst-case recovery time
        # is the worst recovery window in virtual seconds
        "recovery_time_s": float(max(s.recovery_steps, default=0)),
        "tokens_bitwise_vs_unfaulted": all(
            np.array_equal(ft, rt) and np.array_equal(fu, ru)
            for (ft, fu), (rt, ru) in zip(outs, ref_outs)),
        "tokens_bitwise_vs_server": all(
            np.array_equal(ft, st) for (ft, _), (st, _)
            in zip(outs, server_outs)),
        "trace_records": trace_records,
        "summary": s,
    }


def run(smoke: bool = False, quiet: bool = False, seed: int = 0,
        mixed: bool = False, chaos: bool = False,
        trace_out: str | None = None, metrics_out: str | None = None,
        chaos_trace_out: str | None = None) -> dict:
    import dataclasses

    import jax

    from repro import compat
    from repro.configs import registry
    from repro.core import plan as plan_lib
    from repro.models import build_model
    from repro.obs import export as obs_export
    from repro.obs import registry as obs_registry
    from repro.obs import trace as obs_trace

    n_requests = 4 if smoke else 16
    prompt_len = 6 if smoke else 8
    max_new = 4 if smoke else 16
    max_slots = 2 if smoke else 4
    mean_gap = 1.0 if smoke else 2.0

    cfg = registry.smoke_config("qwen2-1.5b", n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    arrivals, prompts = make_trace(n_requests, mean_gap, prompt_len,
                                   cfg.vocab_size, seed=seed)

    from repro.serving import ServerConfig, server as server_lib
    scfg = ServerConfig(max_slots=max_slots, max_queue=n_requests,
                        max_prompt_len=prompt_len, max_new_tokens=max_new)
    scfg_perop = dataclasses.replace(scfg, fused=False)

    # warmup: compile all paths outside the timed region
    _run_baseline(model, params, prompts[:1], max_new)
    _run_server(model, params, scfg, arrivals[:1], prompts[:1], max_new)
    _run_server(model, params, scfg_perop, arrivals[:1], prompts[:1],
                max_new)
    for kvd in ("bfloat16", "int8"):
        _run_server(model, params, dataclasses.replace(scfg, kv_dtype=kvd),
                    arrivals[:1], prompts[:1], max_new)

    base_outs, base_wall = _run_baseline(model, params, prompts, max_new)
    srv_outs, srv_wall, summary = _run_server(model, params, scfg, arrivals,
                                              prompts, max_new)
    po_outs, po_wall, po_summary = _run_server(model, params, scfg_perop,
                                               arrivals, prompts, max_new)
    # checked AFTER the runs: the kernel guards fire at first call, so a
    # build-time check would report a silently-fallen-back leg as fused
    fused_active = server_lib.step_fns(cfg, fused=scfg.fused).fused_live()

    # -- quantized-KV legs: same trace, compressed slot-pool cache ----------
    # bf16 rides the fused decode step; int8 (per-position scale leaves)
    # serves through the per-op fallback. Gate for both: tokens identical
    # to the f32-cache fused leg; and the bf16 spec must model strictly
    # fewer decode HBM bytes at the f32 master width.
    scfg_kv16 = dataclasses.replace(scfg, kv_dtype="bfloat16")
    kv16_outs, kv16_wall, _ = _run_server(model, params, scfg_kv16, arrivals,
                                          prompts, max_new)
    scfg_kv8 = dataclasses.replace(scfg, kv_dtype="int8")
    kv8_outs, kv8_wall, _ = _run_server(model, params, scfg_kv8, arrivals,
                                        prompts, max_new)
    total_tokens_kv = sum(len(t) for t, _ in srv_outs)
    quantized = {
        "kv_bf16_tok_s": total_tokens_kv / kv16_wall,
        "kv_int8_tok_s": total_tokens_kv / kv8_wall,
        "kv_bf16_tokens_match": all(
            np.array_equal(st, qt) for (st, _), (qt, _)
            in zip(srv_outs, kv16_outs)),
        "kv_int8_tokens_match": all(
            np.array_equal(st, qt) for (st, _), (qt, _)
            in zip(srv_outs, kv8_outs)),
        "kv_max_unc_delta": max(
            float(np.max(np.abs(su - qu)))
            for q_outs in (kv16_outs, kv8_outs)
            for (_, su), (_, qu) in zip(srv_outs, q_outs)),
    }

    mixed_res = None
    if mixed:
        mx_outs, mx_scans, mx_wall, mx_summary = _run_mixed(
            model, params, scfg, arrivals, prompts, max_new, smoke, seed)
        mixed_res = {
            "tokens_match": all(
                np.array_equal(bt, mt) for (bt, _), (mt, _)
                in zip(base_outs, mx_outs)),
            "moments_bitwise": all(
                np.array_equal(np.asarray(pm), np.asarray(dm)) and
                np.array_equal(np.asarray(ps), np.asarray(ds))
                for (pm, ps), (dm, ds) in mx_scans),
            "n_scans": len(mx_scans),
            "total_voxels": mx_summary.total_voxels,
            "voxels_per_s": mx_summary.voxels_per_s,
            "lm_tok_s": sum(len(t) for t, _ in mx_outs) / mx_wall,
            "mean_voxel_occupancy": mx_summary.mean_voxel_occupancy,
            "summary": mx_summary,
        }

    # -- traced replay: same trace, ServerConfig(trace=True) ----------------
    # Gates the tentpole's overhead bounds: (a) tokens (and scan moments,
    # when mixed) bitwise-identical with tracing on vs off — tracing never
    # touches traced jax values; (b) zero additional jit retraces — the
    # step_fns/jit caches key on shapes and config, never on the trace knob.
    tracer = obs_trace.TRACER
    tracer.configure(capacity=1 << 20)
    rt0 = obs_registry.REGISTRY.value("retrace_total")
    scfg_tr = dataclasses.replace(scfg, trace=True)
    if mixed:
        tr_outs, tr_scans, _, _ = _run_mixed(
            model, params, scfg_tr, arrivals, prompts, max_new, smoke, seed)
        trace_tokens_match = all(
            np.array_equal(st, tt) for (st, _), (tt, _)
            in zip(srv_outs, tr_outs)) and all(
            np.array_equal(np.asarray(pm), np.asarray(dm)) and
            np.array_equal(np.asarray(ps), np.asarray(ds))
            for (pm, ps), (dm, ds) in tr_scans)
    else:
        tr_outs, _, _ = _run_server(model, params, scfg_tr, arrivals,
                                    prompts, max_new)
        trace_tokens_match = all(
            np.array_equal(st, tt) for (st, _), (tt, _)
            in zip(srv_outs, tr_outs))
    tracer.disable()
    trace_zero_retrace = \
        obs_registry.REGISTRY.value("retrace_total") == rt0
    trace_records = len(tracer.events())
    if trace_out:
        tracer.export_jsonl(trace_out)

    # -- chaos leg: seeded fault replay through the multi-host router -------
    # (after the trace export — this leg clears and re-fills the ring; its
    # own log goes to chaos_trace_out. Runs before the metrics export so
    # the router_* counters land in the exposition.)
    chaos_res = None
    if chaos:
        chaos_res = _run_chaos(model, params, scfg, arrivals, prompts,
                               max_new, seed, srv_outs,
                               trace_out=chaos_trace_out)

    if metrics_out:
        pathlib.Path(metrics_out).write_text(obs_export.prometheus_text())

    total_tokens = sum(len(t) for t, _ in srv_outs)
    tokens_match = all(np.array_equal(bt, st) for (bt, _), (st, _)
                       in zip(base_outs, srv_outs))
    fused_tokens_match = all(np.array_equal(pt, st) for (pt, _), (st, _)
                             in zip(po_outs, srv_outs))
    max_unc_delta = max(float(np.max(np.abs(bu - su))) for (_, bu), (_, su)
                        in zip(base_outs, srv_outs))
    base_tps = total_tokens / base_wall
    srv_tps = total_tokens / srv_wall
    po_tps = total_tokens / po_wall

    # analytic pool traffic of one decode step (paper's weight-load metric
    # over the slot layout the server actually runs)
    from repro.core.scheduler import SlotSchedule
    tm = SlotSchedule(cfg.mask_samples, max_slots).decode_traffic(
        cfg.d_model, cfg.d_ff, cfg.d_model)

    # modeled per-token HBM bytes of the two decode executors: one pool
    # decode step serves max_slots tokens
    spec = plan_lib.decode_fused_spec(cfg)
    rows = cfg.mask_samples * max_slots
    bytes_fused = plan_lib.decode_traffic(spec, rows, scfg.max_seq,
                                          fused=True).total_bytes / max_slots
    bytes_perop = plan_lib.decode_traffic(spec, rows, scfg.max_seq,
                                          fused=False).total_bytes \
        / max_slots

    # modeled decode bytes of the bf16-KV spec vs the f32 cache, both at
    # the f32 master width (the cache dtype is the only difference)
    spec_kv16 = plan_lib.decode_fused_spec(
        dataclasses.replace(cfg, kv_dtype="bfloat16"))
    quantized["modeled_bytes_per_token_kv_f32"] = plan_lib.decode_traffic(
        spec, rows, scfg.max_seq, 4, fused=True).total_bytes / max_slots
    quantized["modeled_bytes_per_token_kv_bf16"] = plan_lib.decode_traffic(
        spec_kv16, rows, scfg.max_seq, 4, fused=True).total_bytes / max_slots

    if not quiet:
        mode = "smoke" if smoke else "full"
        print(f"[{mode}] {n_requests} requests, Poisson mean gap "
              f"{mean_gap} steps, {max_new} tokens each, "
              f"{max_slots} slots x {cfg.mask_samples} masks")
        print(f"pool FFN decode-step traffic (batch-level): "
              f"{tm.weight_loads} weight loads, "
              f"arithmetic intensity {tm.arithmetic_intensity:.2f}")
        print(f"looped one-shot serve_uncertain: "
              f"{base_tps:8.1f} tok/s  ({base_wall:.3f} s)")
        print(f"server, per-op decode:           "
              f"{po_tps:8.1f} tok/s  ({po_wall:.3f} s)"
              f"  -> {po_tps / base_tps:.2f}x")
        print(f"server, fused decode:            "
              f"{srv_tps:8.1f} tok/s  ({srv_wall:.3f} s)"
              f"  -> {srv_tps / base_tps:.2f}x"
              f"  (active: {fused_active})")
        print(f"modeled decode HBM bytes/token:  fused {bytes_fused:,.0f}  "
              f"per-op {bytes_perop:,.0f}  "
              f"-> {bytes_perop / bytes_fused:.2f}x fewer")
        print(f"tokens identical: vs one-shot {tokens_match}, "
              f"fused vs per-op {fused_tokens_match}   "
              f"max |d rel-unc|: {max_unc_delta:.2e}")
        print(f"traced replay: {trace_records} records, tokens bitwise == "
              f"untraced: {trace_tokens_match}, zero added retraces: "
              f"{trace_zero_retrace}")
        print(f"quantized KV: bf16 {quantized['kv_bf16_tok_s']:.1f} tok/s "
              f"(fused), int8 {quantized['kv_int8_tok_s']:.1f} tok/s "
              f"(per-op); tokens identical: bf16 "
              f"{quantized['kv_bf16_tokens_match']}, int8 "
              f"{quantized['kv_int8_tokens_match']}; modeled bytes/token "
              f"{quantized['modeled_bytes_per_token_kv_f32']:,.0f} (f32 "
              f"cache) -> {quantized['modeled_bytes_per_token_kv_bf16']:,.0f}"
              f" (bf16 cache)")
        print(summary.format())
        if mixed_res is not None:
            print(f"mixed pool: {mixed_res['n_scans']} scans "
                  f"({mixed_res['total_voxels']} voxels) interleaved -> "
                  f"{mixed_res['voxels_per_s']:,.0f} vox/s alongside "
                  f"{mixed_res['lm_tok_s']:.1f} tok/s; "
                  f"scan moments bitwise == direct: "
                  f"{mixed_res['moments_bitwise']}, lm tokens unchanged: "
                  f"{mixed_res['tokens_match']}")
            print(mixed_res["summary"].format())
        if chaos_res is not None:
            print(f"chaos: seeded plan (seed {chaos_res['seed']}) killed "
                  f"host(s) {chaos_res['killed_hosts']} at step(s) "
                  f"{chaos_res['kill_steps']} of {chaos_res['horizon']} -> "
                  f"{chaos_res['host_deaths']} death(s), "
                  f"{chaos_res['retries']} retries, "
                  f"{chaos_res['spills']} spills, "
                  f"{chaos_res['remeshes']} remesh(es); "
                  f"lost {chaos_res['lost']}, shed {chaos_res['shed']}; "
                  f"worst recovery {chaos_res['recovery_time_s']:.0f} "
                  f"virtual s; tokens bitwise == unfaulted: "
                  f"{chaos_res['tokens_bitwise_vs_unfaulted']}, == "
                  f"single-host server: "
                  f"{chaos_res['tokens_bitwise_vs_server']}")
            print(chaos_res["summary"].format())
    return {
        "baseline_tok_s": base_tps,
        "server_tok_s": srv_tps,
        "server_perop_tok_s": po_tps,
        "speedup": srv_tps / base_tps,
        "fused_vs_per_op": srv_tps / po_tps,
        "tokens_match": tokens_match,
        "fused_tokens_match": fused_tokens_match,
        "fused_active": fused_active,
        "max_unc_delta": max_unc_delta,
        "pool_weight_loads": tm.weight_loads,
        "modeled_bytes_per_token_fused": bytes_fused,
        "modeled_bytes_per_token_perop": bytes_perop,
        "summary": summary,
        "perop_summary": po_summary,
        "mixed": mixed_res,
        "chaos": chaos_res,
        "quantized": quantized,
        "trace_records": trace_records,
        "trace_tokens_match": trace_tokens_match,
        "trace_zero_retrace": trace_zero_retrace,
        "registry_snapshot": obs_registry.REGISTRY.snapshot(),
        "provenance": {
            **compat.version_summary(),
            **obs_export.host_provenance(),
            "arch": cfg.arch_id, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "n_masks": cfg.mask_samples,
            "max_slots": max_slots, "max_seq": scfg.max_seq,
            "n_requests": n_requests, "prompt_len": prompt_len,
            "max_new_tokens": max_new, "seed": seed,
            "mode": "smoke" if smoke else "full",
        },
    }


def write_bench_json(out: dict, path: pathlib.Path = BENCH_JSON) -> dict:
    """Emit the canonical BENCH_serving.json perf-trajectory artifact:
    fused vs per-op decode tok/s, request-latency percentiles and modeled
    per-token HBM bytes, stamped with backend + shape provenance so future
    PRs compare like with like."""
    import json

    def pcts(s):
        return {"p50_ms": s.latency_p50_s * 1e3,
                "p99_ms": s.latency_p99_s * 1e3,
                "ttft_p50_ms": s.ttft_p50_s * 1e3}

    payload = {
        "bench": "bench_serving",
        "provenance": out["provenance"],
        "tok_s": {
            "one_shot_loop": out["baseline_tok_s"],
            "server_per_op_decode": out["server_perop_tok_s"],
            "server_fused_decode": out["server_tok_s"],
        },
        "request_latency": {
            "server_per_op_decode": pcts(out["perop_summary"]),
            "server_fused_decode": pcts(out["summary"]),
        },
        "modeled_decode_hbm_bytes_per_token": {
            "per_op": out["modeled_bytes_per_token_perop"],
            "fused": out["modeled_bytes_per_token_fused"],
            "reduction": out["modeled_bytes_per_token_perop"]
            / out["modeled_bytes_per_token_fused"],
        },
        "fused_decode_active": out["fused_active"],
        "tokens_identical_fused_vs_per_op": out["fused_tokens_match"],
        "quantized": out["quantized"],
        "trace": {
            "records": out["trace_records"],
            "tokens_bitwise_identical_vs_untraced":
                out["trace_tokens_match"],
            "zero_added_retraces": out["trace_zero_retrace"],
        },
        "registry_snapshot": out["registry_snapshot"],
    }
    if out.get("mixed") is not None:
        mx = out["mixed"]
        payload["mixed_pool"] = {
            "n_scans": mx["n_scans"],
            "total_voxels": mx["total_voxels"],
            "voxels_per_s": mx["voxels_per_s"],
            "lm_tok_s": mx["lm_tok_s"],
            "mean_voxel_occupancy": mx["mean_voxel_occupancy"],
            "scan_moments_bitwise_vs_direct": mx["moments_bitwise"],
            "lm_tokens_unchanged": mx["tokens_match"],
        }
    if out.get("chaos") is not None:
        ch = out["chaos"]
        payload["chaos"] = {k: ch[k] for k in (
            "n_hosts", "seed", "horizon", "killed_hosts", "kill_steps",
            "requests", "completed", "lost", "shed", "host_deaths",
            "retries", "spills", "remeshes", "steps", "recovery_steps",
            "recovery_time_s", "tokens_bitwise_vs_unfaulted",
            "tokens_bitwise_vs_server")}
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return payload


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI (tier-1-safe, ~seconds)")
    ap.add_argument("--fused", action="store_true",
                    help="gate on the fused decode leg: it must run fused "
                         "(no silent per-op fallback) and match the per-op "
                         "tokens bitwise")
    ap.add_argument("--quantized", action="store_true",
                    help="gate on the quantized-KV legs: bf16/int8 cache "
                         "tokens must match the f32-cache leg and the bf16 "
                         "spec must model strictly fewer decode HBM bytes")
    ap.add_argument("--mixed", action="store_true",
                    help="add the mixed-modality leg: IVIM scans as "
                         "voxel-chunk work items in the same pool; gates on "
                         "bitwise scan moments and unchanged LM tokens")
    ap.add_argument("--chaos", action="store_true",
                    help="add the fault-tolerance leg: seeded FaultPlan "
                         "replay through the 3-host router; gates on zero "
                         "lost/shed requests and bitwise-identical "
                         "recovered tokens")
    ap.add_argument("--seed", type=int, default=0,
                    help="trace seed (arrivals, prompts, scan volumes); "
                         "recorded in the JSON provenance")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the traced replay's span/event log as "
                         "JSONL (benchmarks/verify_obs.py replays it)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the telemetry registry as Prometheus text "
                         "exposition after the run")
    ap.add_argument("--chaos-trace-out", default=None, metavar="PATH",
                    help="write the faulted chaos run's span/event log as "
                         "JSONL (verify_obs.py checks the host-death -> "
                         "retry -> re-admit lifecycle)")
    args = ap.parse_args()
    res = run(smoke=args.smoke, seed=args.seed, mixed=args.mixed,
              chaos=args.chaos, trace_out=args.trace_out,
              metrics_out=args.metrics_out,
              chaos_trace_out=args.chaos_trace_out)
    if not res["trace_tokens_match"]:
        print("ERROR: tokens/moments changed when span tracing was "
              "enabled (tracing must be bitwise-invisible)")
        return 1
    if not res["trace_zero_retrace"]:
        print("ERROR: enabling span tracing added jit retraces "
              "(retrace_total moved during the traced replay)")
        return 1
    if not res["tokens_match"]:
        print("ERROR: server tokens diverged from one-shot serving")
        return 1
    if not res["fused_tokens_match"]:
        print("ERROR: fused-decode server tokens diverged from the per-op "
              "decode server")
        return 1
    if res["max_unc_delta"] > 1e-4:
        print(f"ERROR: per-token uncertainty diverged beyond fp32 tolerance "
              f"({res['max_unc_delta']:.2e} > 1e-4)")
        return 1
    if args.fused and not res["fused_active"]:
        print("ERROR: --fused requested but the fused decode step was not "
              "selected (FusedPlanUnsupported fallback)")
        return 1
    if args.fused and res["modeled_bytes_per_token_fused"] >= \
            res["modeled_bytes_per_token_perop"]:
        print("ERROR: fused decode step models no HBM-byte reduction")
        return 1
    if args.quantized:
        q = res["quantized"]
        if not (q["kv_bf16_tokens_match"] and q["kv_int8_tokens_match"]):
            print("ERROR: quantized-KV server tokens diverged from the "
                  "f32-cache leg")
            return 1
        if q["kv_max_unc_delta"] > 1e-3:
            print(f"ERROR: quantized-KV uncertainty diverged beyond "
                  f"tolerance ({q['kv_max_unc_delta']:.2e} > 1e-3)")
            return 1
        if q["modeled_bytes_per_token_kv_bf16"] >= \
                q["modeled_bytes_per_token_kv_f32"]:
            print("ERROR: bf16 KV cache models no decode HBM-byte "
                  "reduction over the f32 cache")
            return 1
    if args.chaos:
        ch = res["chaos"]
        if ch["lost"] or ch["shed"]:
            print(f"ERROR: chaos run lost {ch['lost']} and shed "
                  f"{ch['shed']} request(s) — fault tolerance must not "
                  f"drop work")
            return 1
        if ch["host_deaths"] < 1 or ch["retries"] < 1:
            print(f"ERROR: chaos scenario exercised {ch['host_deaths']} "
                  f"host death(s) and {ch['retries']} retries — the "
                  f"seeded plan must actually kill a host holding work")
            return 1
        if not ch["tokens_bitwise_vs_unfaulted"] or \
                not ch["tokens_bitwise_vs_server"]:
            print("ERROR: recovered tokens diverged from the unfaulted "
                  "reference (failover must be bitwise-invisible)")
            return 1
    if args.mixed:
        if not res["mixed"]["moments_bitwise"]:
            print("ERROR: pooled scan moments diverged from the direct "
                  "predict_volume path (must be bitwise-identical)")
            return 1
        if not res["mixed"]["tokens_match"]:
            print("ERROR: LM tokens changed when scans were interleaved "
                  "into the pool")
            return 1
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
