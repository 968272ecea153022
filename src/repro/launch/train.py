"""Production training launcher.

On a real TPU slice this is the per-host entry point (jax.distributed
initializes from the TPU environment); on this container it runs the same
code path on the host mesh.  All fault-tolerance machinery is live:
restore-from-latest, periodic async checkpoints, SIGTERM flush, straggler
watchdog, elastic restore under a different mesh shape.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b --reduced \
      --steps 100 --ckpt-dir results/ckpt_qwen2
"""
from __future__ import annotations

import argparse
import functools
import logging

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs import SHAPES, get_config
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim.schedule import cosine_with_warmup
from repro.parallel.presets import parallelism_for
from repro.runtime.loop import LoopConfig, run_training


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config (default on a host-only run)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--peak-lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 production mesh (TPU slice)")
    args = ap.parse_args()
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.data_mesh, args.model_mesh))
    pcfg = parallelism_for(cfg, SHAPES["train_4k"],
                           model_axis=mesh.shape.get("model", 1))
    data = SyntheticLM(cfg, args.batch, args.seq, seed=0)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    lr = functools.partial(cosine_with_warmup, peak_lr=args.peak_lr,
                           warmup_steps=max(args.steps // 10, 1),
                           total_steps=args.steps)
    res = run_training(cfg, pcfg, mesh, data,
                       LoopConfig(total_steps=args.steps,
                                  checkpoint_every=args.checkpoint_every),
                       ckpt=ckpt, lr_fn=lr)
    print(f"final loss {res.losses[-1]:.4f} after {res.final_step} steps; "
          f"stragglers={res.straggler_events}"
          + (f"; resumed from {res.restored_from}" if res.restored_from else ""))


if __name__ == "__main__":
    main()
