"""The paper's technique at LM scale: mask-based Bayesian *serving* with
per-token uncertainty, on any assigned architecture (reduced config, or
its published widths with ``--full``).

    PYTHONPATH=src python examples/serve_uncertainty_lm.py \
        [--arch qwen2-1.5b] [--full] [--tokens 12] [--server] \
        [--trace-out trace.jsonl] [--metrics-out metrics.prom]

Every request is evaluated under N fixed Masksembles masks (no runtime RNG);
the decode loop reports the relative uncertainty of each emitted token and
flags tokens above the threshold — the LM analogue of the paper's clinical
escalation pathway.

Default mode drives the one-shot engine (`serve_uncertain`: one fixed batch
to completion). ``--server`` drives the same requests through the
continuous-batching server instead — an admission queue feeding a
``N_masks x max_slots`` KV slot pool with jitted fixed-shape steps — and
prints the serving metrics (tokens/s, latency percentiles, slot occupancy).
Both paths produce identical tokens and uncertainties; the server is how
the batch-level mask schedule amortizes over live traffic.

``--full`` serves the registry config at its published widths (bf16,
seeded random weights, N masks on) instead of the reduced smoke config —
qwen2-1.5b needs about 3.1 GB of device memory for its weights, so run it
on the chip.

``--scan`` (with ``--server``) additionally submits a synthetic IVIM scan
volume into the SAME pool as a voxel-chunk work item (``submit_scan``): one
slot, one fused-moments chunk per engine step, sharing the LM requests'
queue, backpressure and escalation policy. The example prints per-modality
latency and uncertainty summaries — the paper's MRI workload and its LM
analogue served by one scheduler.

``--hosts N`` (with ``--server``) fronts N per-host pools with the
fault-tolerant multi-host router (``repro.serving.router``): sticky
round-robin request homes, cross-host spill on backpressure, heartbeat
health checks on a virtual clock, and bounded retry/backoff failover.
``--chaos`` scripts a host kill mid-run through the deterministic
fault-injection harness (``repro.serving.faults``) — the example then
shows the death being detected, the resident work resubmitted, the pool
remeshed (``distributed.elastic.plan_remesh``), and the recovered tokens
coming back identical anyway (pool rows are batch-independent, so
failover is bitwise-invisible).

``--trace-out`` (with ``--server``) switches on the observability layer
(``repro.obs``): every enqueue / admit / prefill / decode / token /
escalation / finish lands in a JSONL span log that
``benchmarks/verify_obs.py`` can replay; ``--metrics-out`` writes the
telemetry registry as Prometheus text exposition.
"""

import argparse

import jax
import numpy as np

from repro import compat
from repro.configs import registry
from repro.models import build_model
from repro.serving import (BayesianLMServer, ServeConfig, ServerConfig,
                           serve_uncertain)


def _print_request(i, tokens, uncs, flags, threshold):
    toks = " ".join(f"{int(t):4d}" for t in tokens)
    unc = " ".join(f"{float(u):4.2f}" for u in uncs)
    flg = " ".join("   ^" if bool(f) else "    " for f in flags)
    print(f"req {i}: tokens  {toks}")
    print(f"       rel-unc {unc}")
    if any(flags):
        print(f"               {flg}  <- above threshold "
              f"{threshold} (escalate)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=list(registry.ARCH_IDS))
    ap.add_argument("--full", action="store_true",
                    help="published widths (bf16) instead of the reduced "
                         "smoke config")
    ap.add_argument("--tokens", type=int, default=12)
    ap.add_argument("--n-masks", type=int, default=4)
    ap.add_argument("--threshold", type=float, default=0.35)
    ap.add_argument("--server", action="store_true",
                    help="route requests through the continuous-batching "
                         "server (queue -> slots -> mask groups)")
    ap.add_argument("--requests", type=int, default=4,
                    help="request count in --server mode")
    ap.add_argument("--slots", type=int, default=2,
                    help="KV slot-pool size in --server mode")
    ap.add_argument("--hosts", type=int, default=1,
                    help="front N per-host pools with the fault-tolerant "
                         "router (--server mode; 1 = single server)")
    ap.add_argument("--chaos", action="store_true",
                    help="script a host kill mid-run (--hosts > 1): the "
                         "router detects the death by heartbeat, resubmits "
                         "the work, remeshes — results are unchanged")
    ap.add_argument("--scan", action="store_true",
                    help="also submit a synthetic IVIM scan volume into the "
                         "same pool (--server mode): voxel chunks and LM "
                         "tokens share slots, queue and escalation policy")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="(--server mode) enable span tracing and write the "
                         "request-lifecycle event log as JSONL")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="(--server mode) write the telemetry registry as "
                         "Prometheus text exposition after the run")
    args = ap.parse_args()
    if args.scan and not args.server:
        raise SystemExit("--scan needs --server (the scan rides the pool)")
    if args.hosts > 1 and not args.server:
        raise SystemExit("--hosts needs --server (the router fronts pools)")
    if args.chaos and args.hosts < 2:
        raise SystemExit("--chaos needs --hosts >= 2 (a surviving host "
                         "must pick up the dead host's work)")
    if (args.trace_out or args.metrics_out) and not args.server:
        raise SystemExit("--trace-out/--metrics-out need --server (the "
                         "one-shot engine has no request lifecycle)")

    compat.enable_compilation_cache()
    make_cfg = registry.get_config if args.full else registry.smoke_config
    cfg = make_cfg(args.arch, mask_samples=args.n_masks)
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only; pick a decoder arch")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"arch={args.arch} "
          f"({'published widths' if args.full else 'reduced'}), "
          f"N={args.n_masks} fixed masks")

    if args.server:
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.requests, 8), 0, cfg.vocab_size)
        scfg = ServerConfig(
            max_slots=args.slots, max_prompt_len=8,
            max_new_tokens=args.tokens,
            uncertainty_threshold=args.threshold,
            trace=bool(args.trace_out))
        use_router = args.hosts > 1
        clock = None
        if use_router:
            from repro.obs.trace import ManualClock
            from repro.serving import (FaultEvent, FaultPlan, RouterConfig,
                                       ServingRouter)
            faults = FaultPlan()
            if args.chaos:
                faults = FaultPlan(events=(
                    FaultEvent(step=2, host=0, action="kill"),))
                print(f"chaos: host 0 goes silent at router step 2 "
                      f"({args.hosts - 1} host(s) survive)")
            clock = ManualClock()
            server = ServingRouter(
                model, params, scfg,
                RouterConfig(n_hosts=args.hosts, heartbeat_timeout_s=2.5,
                             max_retries=3),
                faults=faults, clock=clock)
            print(f"router: {args.hosts} hosts x {args.slots} slots, "
                  f"heartbeat timeout 2.5 virtual s")
        else:
            server = BayesianLMServer(model, params, scfg)
        rids = [server.submit(p) for p in prompts]
        sid = None
        if args.scan:
            from repro.ivim import model as ivim_model
            icfg = ivim_model.IvimConfig(n_masks=args.n_masks, scale=2.0)
            iparams, istate = ivim_model.init(icfg, jax.random.PRNGKey(2))
            plan = ivim_model.pack_for_serving(icfg, iparams, istate)
            shape = (8, 8, 4)                       # synthetic IVIM volume
            vol = np.random.default_rng(3).uniform(
                size=shape + (icfg.width,)).astype(np.float32)
            sid = server.submit_scan(plan, vol.reshape(-1, icfg.width),
                                     chunk=64)
            print(f"scan: {shape} IVIM volume ({vol[..., 0].size} voxels, "
                  f"{icfg.width} b-values) as one voxel-chunk work item")
        if use_router:
            summary = server.run(max_steps=10_000,
                                 tick=lambda: clock.advance(1.0))
        else:
            summary = server.run()

        def _state(rid):
            return server.result(rid).final if use_router \
                else server.result(rid)

        total_flagged = 0
        for i, rid in enumerate(rids):
            st = _state(rid)
            _print_request(i, st.generated, st.uncertainty, st.flags,
                           args.threshold)
            total_flagged += sum(st.flags)
        print(f"\nflagged {total_flagged}/"
              f"{sum(len(_state(r).generated) for r in rids)} tokens"
              f" for review")
        if sid is not None:
            st = _state(sid)
            mean, std = st.scan_moments()
            rel = np.asarray(std) / np.maximum(np.abs(np.asarray(mean)),
                                               1e-12)
            print(f"\n-- scan (req {sid}) --")
            print(f"chunks    {len(st.chunk_results)} "
                  f"({sum(st.flags)} flagged above {args.threshold}, "
                  f"{st.preempts} preemptions)")
            if not use_router:      # per-request timelines are per-host
                tl = server.metrics.timelines
                print(f"latency   {tl[sid].latency * 1e3:.1f} ms "
                      f"(queue wait {tl[sid].queue_wait * 1e3:.1f} ms)")
                lm_lat = [tl[r].latency for r in rids]
                print(f"lm latency alongside   p50 "
                      f"{np.percentile(lm_lat, 50) * 1e3:.1f} ms")
            print(f"voxel rel-unc   mean {rel.mean():.3f}   "
                  f"max {rel.max():.3f}")
        print(f"\n-- serving metrics ({args.slots} slots x "
              f"{args.n_masks} mask rows each) --")
        print(summary.format())
        if args.trace_out:
            from repro.obs import trace as obs_trace
            n = obs_trace.TRACER.export_jsonl(args.trace_out)
            print(f"\nwrote {n} trace records -> {args.trace_out}  "
                  f"(verify: python -m benchmarks.verify_obs "
                  f"--trace {args.trace_out})")
        if args.metrics_out:
            from repro.obs import export as obs_export
            with open(args.metrics_out, "w") as f:
                f.write(obs_export.prometheus_text())
            print(f"wrote metrics exposition -> {args.metrics_out}")
        return

    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    gen, unc, flags = serve_uncertain(
        model, params, prompts,
        ServeConfig(max_new_tokens=args.tokens,
                    uncertainty_threshold=args.threshold))
    for i in range(gen.shape[0]):
        _print_request(i, gen[i, 8:], unc[i], flags[i], args.threshold)
    print(f"\nflagged {int(flags.sum())}/{flags.size} tokens for review")


if __name__ == "__main__":
    main()
