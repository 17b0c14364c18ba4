"""End-to-end LM training driver on an assigned architecture.

Runs the full production path — config registry, data pipeline,
grad-accumulated train step, checkpointing with auto-resume,
straggler monitor — on CPU-sized settings by default.

    PYTHONPATH=src python examples/train_lm.py                  # quick
    PYTHONPATH=src python examples/train_lm.py --arch smollm_135m \
        --full --steps 300 --batch 8 --seq 256                  # ~135M

``--arch`` accepts any of the 10 assigned architectures; ``--full``
uses the exact published config (CPU: expect minutes/step for the
big ones — the multi-pod path is exercised by launch/dryrun.py).
"""
import argparse

from repro.configs import ARCHS, get_config, get_smoke
from repro.launch.train import train
from repro.optim import AdamWConfig
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="smollm_135m")
    ap.add_argument("--full", action="store_true",
                    help="exact published config (default: smoke size)")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None,
                    help="enable checkpoint/auto-resume")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_smoke(args.arch)
    print(f"arch={cfg.name}  layers={cfg.n_layers}  d={cfg.d_model}  "
          f"steps={args.steps}  batch={args.batch}x{args.seq}")

    out = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                opt_cfg=AdamWConfig(lr=args.lr, total_steps=args.steps),
                ckpt_dir=args.ckpt_dir, n_micro=args.n_micro,
                log_every=max(1, args.steps // 10))

    first = sum(out["losses"][:5]) / max(1, len(out["losses"][:5]))
    last = sum(out["losses"][-5:]) / max(1, len(out["losses"][-5:]))
    print(f"\nloss {first:.4f} -> {last:.4f} over {args.steps} steps "
          f"({out['runtime_s']:.1f}s, "
          f"{out['runtime_s'] / max(1, args.steps):.2f}s/step)")
    assert last < first, "loss did not decrease"


if __name__ == "__main__":
    main()
