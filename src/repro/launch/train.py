"""Training driver.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \\
      --steps 200 --batch 8 --seq 128 --ckpt /tmp/ck

Full-scale configs need the production mesh (real TPUs); `--reduced` runs the
same code path end-to-end on this CPU container.  The trainer checkpoints
atomically and auto-resumes from the newest checkpoint in --ckpt.
"""

from __future__ import annotations

import argparse


from repro import configs as C
from repro.data.pipeline import DataConfig
from repro.env import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.optim.adamw import OptConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--photonic-mac", action="store_true",
                    help="route linears through the photonic-MAC QAT numerics")
    ap.add_argument("--wire-bits", type=int, default=0,
                    help="int8/bf16 parameter wire format (8 or 16)")
    ap.add_argument("--moe-dispatch", choices=["einsum", "index"], default=None)
    ap.add_argument("--data-file", default=None,
                    help="mmap token corpus (.bin uint16); default synthetic")
    ap.add_argument("--mesh", choices=["none", "single", "multi"], default="none")
    args = ap.parse_args()

    cfg = C.get_reduced(args.arch) if args.reduced else C.get(args.arch)
    import dataclasses
    if args.photonic_mac:
        cfg = dataclasses.replace(cfg, use_photonic_mac=True)
    if args.wire_bits:
        cfg = dataclasses.replace(cfg, wire_bits=args.wire_bits)
    if args.moe_dispatch:
        cfg = dataclasses.replace(cfg, moe_dispatch=args.moe_dispatch)

    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    data = DataConfig(global_batch=args.batch, seq_len=args.seq)
    source = None
    if args.data_file:
        from repro.data.filesource import TokenFileSource
        source = TokenFileSource(cfg, data, args.data_file)

    trainer = Trainer(
        cfg,
        OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                  total_steps=args.steps),
        data,
        TrainerConfig(ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every),
        mesh=mesh,
        resume=not args.no_resume,
        source=source,
    )
    out = trainer.run(args.steps)
    print(f"done: {out}")


if __name__ == "__main__":
    main()
