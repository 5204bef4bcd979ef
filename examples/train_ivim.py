"""End-to-end paper reproduction driver: train uIVIM-NET and reproduce
Figs. 6-7 (RMSE + uncertainty vs SNR) with the Phase-2 requirement gate.

    PYTHONPATH=src python examples/train_ivim.py [--steps 800] [--n-masks 4]
"""

import argparse

from repro import compat
from repro.ivim import evaluate as E, model as M, train as T


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--n-masks", type=int, default=4)
    ap.add_argument("--scale", type=float, default=2.0)
    ap.add_argument("--dense-protocol", action="store_true",
                    help="use the 104-b-value research protocol")
    args = ap.parse_args()
    compat.enable_compilation_cache()

    from repro.ivim import physics
    b_values = (physics.DENSE_B_VALUES if args.dense_protocol
                else physics.CLINICAL_B_VALUES)
    cfg = M.IvimConfig(b_values=b_values, n_masks=args.n_masks,
                       scale=args.scale)
    print(f"training uIVIM-NET: {len(b_values)} b-values, "
          f"N={args.n_masks}, scale={args.scale}, {args.steps} steps")
    params, state, hist = T.train(cfg, T.TrainConfig(
        steps=args.steps, batch_size=128, lr=3e-3), log_every=100)

    results = E.evaluate_snr_sweep(cfg, params, state, n_voxels=2000)
    print(f"\n{'SNR':>5s} {'RMSE':>8s} " +
          "".join(f"{'rmse_' + p:>10s}" for p in M.PARAM_NAMES) +
          "".join(f"{'unc_' + p:>10s}" for p in M.PARAM_NAMES))
    for snr in sorted(results):
        r = results[snr]
        print(f"{snr:5.0f} {r['rmse_recon']:8.4f} " +
              "".join(f"{r['rmse_params'][p]:10.5f}"
                      for p in M.PARAM_NAMES) +
              "".join(f"{r['rel_unc'][p]:10.4f}" for p in M.PARAM_NAMES))
    report = E.requirement_report(results)
    print(f"\nPhase-2 gate (paper Figs. 6-7 trends): "
          f"{'SATISFIED' if report.satisfied else 'NOT satisfied'}")
    for fail in report.failures:
        print("  -", fail)


if __name__ == "__main__":
    main()
