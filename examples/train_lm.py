"""End-to-end LM training driver: a small decoder of any assigned family,
trained for a few hundred steps on CPU with the full production stack —
Masksembles-FFN, AdamW + cosine schedule, grad accumulation, atomic
checkpoints with auto-resume, straggler monitoring.

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --steps 200 --d-model 512 \
        --layers 8   # ~100M params (slower on CPU)

Kill it mid-run and re-launch: it resumes from the last committed
checkpoint with bit-identical data (stateless seeded pipeline).
"""

import argparse
import dataclasses

import jax.numpy as jnp

from repro import compat
from repro.configs import registry
from repro.data import LMDataConfig
from repro.models import build_model
from repro.optim import OptimizerConfig, build_optimizer
from repro.train import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=list(registry.ARCH_IDS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--grad-accum", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()
    compat.enable_compilation_cache()

    heads = max(4, args.d_model // 32)
    cfg = registry.smoke_config(
        args.arch, d_model=args.d_model, n_layers=args.layers,
        n_heads=heads, n_kv_heads=max(1, heads // 2), head_dim=32,
        d_ff=0 if registry.get_config(args.arch).d_ff == 0
        else 4 * args.d_model,
        vocab_size=512, dtype=jnp.float32)
    model = build_model(cfg)
    n_params = sum(x.size for x in
                   __import__("jax").tree.leaves(
                       model.param_specs()))
    # checkpoints are shape-checked on restore; key the dir by the config so
    # changing flags doesn't collide with an old run's checkpoints
    args.ckpt_dir = f"{args.ckpt_dir}_{args.arch}_{n_params}"
    print(f"arch={args.arch} family={cfg.family} params={n_params/1e6:.1f}M "
          f"masksembles N={cfg.mask_samples}")

    optimizer = build_optimizer(OptimizerConfig(
        lr=1e-3, warmup_steps=20, decay_steps=args.steps))
    data = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                        global_batch=args.batch)
    trainer = Trainer(model, optimizer,
                      TrainConfig(steps=args.steps,
                                  grad_accum=args.grad_accum,
                                  checkpoint_dir=args.ckpt_dir,
                                  checkpoint_every=50), data)

    def on_step(rec):
        if rec["step"] % 20 == 0 or rec["straggler"] != "ok":
            print(f"step {rec['step']:5d}  loss {rec['loss']:.4f}  "
                  f"{rec['time_s']*1e3:6.1f} ms  [{rec['straggler']}]")

    state, history = trainer.run(on_step=on_step)
    print(f"done: loss {history[0]['loss']:.4f} -> "
          f"{history[-1]['loss']:.4f}; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
