"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

On this CPU container it runs reduced configs end-to-end; on real TPU pods
the same entry point builds the production mesh and the full config (the
code path is identical — only ``--mesh`` changes).
"""
from __future__ import annotations

import argparse


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fpnew-case-study")
    ap.add_argument("--policy", default="tp_bf16")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", default=None,
                    help="fp8|fp16alt: compressed DP gradient sync")
    ap.add_argument("--mesh", choices=["none", "pod1", "pod2"],
                    default="none")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    args = ap.parse_args(argv)

    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.mesh != "none":
        from .mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.mesh == "pod2")
    else:
        mesh = None

    from ..data.pipeline import DataConfig
    from ..models.registry import build_model
    from ..optim.optimizer import OptConfig
    from ..train.loop import LoopConfig, TrainLoop

    model = build_model(args.arch, policy=args.policy, reduced=args.reduced)
    opt = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                    total_steps=args.steps)
    data = DataConfig(vocab=model.cfg.vocab, seq_len=args.seq_len,
                      global_batch=args.global_batch)
    lc = LoopConfig(total_steps=args.steps,
                    log_every=max(args.steps // 20, 1),
                    ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                    compress_grads=args.compress_grads)
    loop = TrainLoop(model, opt, data, lc, mesh=mesh)
    log = loop.run()
    print(f"done: {len(log)} steps, final loss {log[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
