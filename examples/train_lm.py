"""End-to-end training driver: train a ~100M-param LM for a few hundred
steps with the full production stack (pjit shardings, remat, bf16
compression, async checkpointing, restart).

    PYTHONPATH=src python examples/train_lm.py --arch qwen3_0_6b \
        --steps 300 --d-model 256 --layers 8

Any assigned architecture id works (--arch); by default the config is
scaled to ~100M params so a few hundred steps finish on CPU. Re-running
with the same --ckpt-dir resumes from the latest checkpoint.
"""

import argparse
import dataclasses

from repro.configs.base import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.train import TrainHParams, run_training


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    period = len(cfg.layer_pattern)
    layers = max(period, (args.layers // period) * period)
    n_heads = max(4, (args.d_model // 64) // 4 * 4)   # divisible by kv=4
    cfg = dataclasses.replace(
        cfg, n_layers=layers, d_model=args.d_model,
        n_heads=n_heads, n_kv=4, head_dim=64,
        d_ff=4 * args.d_model if cfg.d_ff else 0, vocab=args.vocab,
        num_patches=0, encoder_layers=0, encoder_frames=0)

    mesh = make_local_mesh()
    hp = TrainHParams(lr=args.lr, warmup=20, total_steps=args.steps)

    def log(step, metrics):
        print(f"step {step:5d}  loss {metrics['loss']:.4f}  "
              f"gnorm {metrics['gnorm']:.2f}  lr {metrics['lr']:.2e}",
              flush=True)

    final = run_training(cfg, mesh, hp, global_batch=args.batch,
                         seq_len=args.seq_len, steps=args.steps,
                         ckpt_dir=args.ckpt_dir, ckpt_every=50,
                         on_metrics=log, log_every=10)
    print("final metrics:", {k: round(v, 4) for k, v in final.items()})


if __name__ == "__main__":
    main()
