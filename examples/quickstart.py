"""Quickstart: the paper's flow in 40 lines.

    PYTHONPATH=src python examples/quickstart.py

1. Generate synthetic diffusion-MRI voxels from the IVIM equation (Eq. 1).
2. Convert IVIM-NET -> uIVIM-NET (fixed Masksembles masks) and train it
   with the physics reconstruction loss.
3. Predict IVIM parameters WITH uncertainty.
4. Phase 3: fold BN, apply mask-zero skipping, serve batch-level — verify
   the packed serving path is numerically identical.
"""

import jax
import numpy as np

from repro import compat
from repro.ivim import data as ivim_data, model as ivim_model
from repro.ivim import train as ivim_train


def main() -> None:
    compat.enable_compilation_cache()
    # Phase 1: synthetic scenario (SNR 20) + uncertainty requirements
    ds = ivim_data.make_dataset(ivim_data.SyntheticConfig(
        n_voxels=4000, snr=20.0, seed=0))

    # Phase 2: DNN -> mask-based BayesNN, physics-loss training
    cfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state, hist = ivim_train.train(
        cfg, ivim_train.TrainConfig(steps=300, batch_size=128, lr=3e-3),
        dataset=ds, log_every=100)
    print(f"loss: {hist[0]:.4f} -> {hist[-1]:.4f}")

    # predict with uncertainty
    x = ds["signals"][:8]
    mean, std = ivim_model.predict(cfg, params, state, x)
    for i, name in enumerate(ivim_model.PARAM_NAMES):
        print(f"{name:>6s}: {np.asarray(mean[0, i]):.5f} "
              f"+/- {np.asarray(std[0, i]):.5f} "
              f"(truth {np.asarray(ds['params'][name][0]):.5f})")

    # Phase 3: compile to a PackedPlan (mask-zero skipping + batch-level
    # schedule, dispatched through the masked_ffn kernel stack)
    plan = ivim_model.pack_for_serving(cfg, params, state)
    served = ivim_model.packed_apply(plan, x)
    ref = ivim_model.apply_all_samples(cfg, params, state, x)
    err = float(np.abs(np.asarray(served) - np.asarray(ref)).max())
    keep = plan.pairs[0].keep
    print(f"packed serving: hidden {cfg.width} -> {keep} units/sample, "
          f"max|err| vs training form = {err:.2e}")

    # Serve a whole scan: voxel chunks stream through the fused whole-plan
    # megakernel (one launch per chunk, in-kernel moments — the [N, B, 4]
    # sample tensor is never materialized).
    from repro.serving import engine
    nx, ny, nz = 16, 16, 2
    volume = ds["signals"][: nx * ny * nz].reshape(nx, ny, nz, cfg.width)
    vmean, vstd = engine.predict_volume(plan, volume, chunk=128)
    print(f"volume serving: {volume.shape} -> mean/std {vmean.shape}, "
          f"D at center = {np.asarray(vmean[nx // 2, ny // 2, 0, 0]):.5f} "
          f"+/- {np.asarray(vstd[nx // 2, ny // 2, 0, 0]):.5f}")


if __name__ == "__main__":
    main()
