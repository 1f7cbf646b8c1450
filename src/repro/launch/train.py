"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
        --steps 100 --batch 8 --seq 128

Runs the real Trainer on the host devices.  ``--mesh host`` wraps the
step in pjit over whatever devices exist (data-parallel); the production
mesh path is exercised by dryrun.py.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config
from ..data import TokenStream
from ..models import build_model
from ..models.frontends import fake_audio_frames, fake_vision_patches
from ..training import Trainer
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
    model = build_model(cfg)
    trainer = Trainer(model, peak_lr=args.lr, warmup=max(args.steps // 10, 1),
                      total_steps=args.steps)

    extra = None
    if cfg.family == "audio":
        extra = fake_audio_frames(cfg, args.batch)
    elif cfg.vision_seq:
        extra = fake_vision_patches(cfg, args.batch)

    stream = TokenStream(cfg.vocab_size, args.seq, args.batch, seed=0)

    def batches():
        for b in stream:
            if extra is not None:
                b = dict(b, extra_embeds=extra)
            yield b

    hist = trainer.fit(batches(), steps=args.steps, log_every=args.log_every)
    print(f"final loss: {hist[-1]['loss']:.4f} "
          f"(start {hist[0]['loss']:.4f})")
    if args.ckpt_dir:
        from ..checkpoint import save_checkpoint
        path = save_checkpoint(args.ckpt_dir, args.steps, trainer.state.params)
        print(f"checkpoint: {path}")


if __name__ == "__main__":
    main()
