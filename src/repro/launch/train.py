"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Small-scale (this host) runs use reduced configs by default; pass
``--full`` to build the full assigned config (requires a real cluster —
the mesh/shardings are exactly the dry-run's).
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import SHAPES, get_config, reduced
from repro.data import make_pipeline
from repro.launch.compile_cache import use_compile_cache
from repro.models.model_zoo import build
from repro.train import TrainOptions, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, required=True)
    ap.add_argument("--shape", type=str, default="train_4k",
                    choices=[k for k, v in SHAPES.items()
                             if v.kind == "train"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--full", action="store_true",
                    help="full config (cluster scale); default: reduced")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    if not args.full:
        cfg = reduced(cfg)
        seq, batch = args.seq_len, args.batch
    else:
        seq, batch = shape.seq_len, shape.global_batch

    print(f"arch={cfg.name} family={cfg.family} "
          f"N={cfg.param_count()/1e6:.0f}M seq={seq} batch={batch} "
          f"schedule={cfg.lr_schedule}")

    api = build(cfg)

    class _Pipe:
        def __init__(self, inner):
            self.inner = inner

        def batch(self, step):
            return api.make_batch(jax.random.fold_in(
                jax.random.PRNGKey(0), step), batch, seq)

    options = TrainOptions(peak_lr=args.lr, warmup_steps=10,
                           total_steps=max(args.steps, 20),
                           grad_accum=args.grad_accum,
                           schedule=cfg.lr_schedule)
    trainer = Trainer(api, options, pipeline=_Pipe(None),
                      ckpt_dir=args.ckpt_dir, donate=False)
    state = trainer.init_or_restore(jax.random.PRNGKey(0))
    state, hist = trainer.run(state, steps=args.steps,
                              ckpt_every=args.ckpt_every if args.ckpt_dir
                              else 0, log_every=10)
    print(f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
