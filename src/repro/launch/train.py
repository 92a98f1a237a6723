"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --steps 100 --batch 8 --seq 256 --reduced

On a real multi-host TPU deployment this module is the per-host entry
point: jax.distributed initialisation, production mesh, per-host data
sharding, fault-tolerant trainer with elastic re-mesh.  ``--reduced``
swaps in the reduced config so the same path runs on CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.configs.base import get_arch, with_precision
from repro.data.pipeline import DataConfig
from repro.launch import args as largs
from repro.launch import compile_cache
from repro.launch.mesh import (dp_axes_for, make_mesh_for_devices,
                               make_production_mesh)
from repro.optim.adamw import AdamWConfig
from repro.train.step import LossScaleConfig
from repro.train.trainer import ElasticTrainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 / 2x16x16 production mesh "
                         "(requires 256/512 devices)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--distributed", action="store_true",
                    help="initialise jax.distributed from env (multi-host)")
    largs.add_precision_args(ap)
    largs.add_tuning_args(ap)
    largs.add_observability_args(ap)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    compile_cache.enable()
    largs.setup_observability(args)
    largs.load_tune_cache(args, "train")

    if args.distributed:
        jax.distributed.initialize()

    entry = get_arch(args.arch)
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = make_mesh_for_devices(jax.devices(),
                                     model_parallel=min(
                                         16, len(jax.devices())))
    dp_axes = dp_axes_for(mesh)
    tp = mesh.shape["model"]

    cfg = entry.reduced() if args.reduced else entry.full(n_model_shards=tp)
    cfg = dataclasses.replace(cfg, n_model_shards=tp, max_seq=args.seq)
    mp_kwargs = {}
    if args.precision:
        cfg = with_precision(cfg, args.precision)
        if cfg.param_dtype != jax.numpy.float32:
            # low-precision params need the f32 master + loss-scale loop
            mp_kwargs = dict(master_weights=True,
                             loss_scaling=LossScaleConfig())

    n_hosts = jax.process_count()
    trainer = ElasticTrainer(
        cfg,
        AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                    total_steps=args.steps),
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch, n_hosts=n_hosts,
                   host_id=jax.process_index(),
                   vision_len=args.seq // 2 if cfg.family == "vlm" else 0,
                   enc_len=cfg.enc_len if cfg.family == "audio" else 0,
                   d_model=cfg.d_model),
        TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
        mesh=mesh, dp_axes=dp_axes,
        grad_compression=args.grad_compression,
        mesh_builder=lambda devs: make_mesh_for_devices(
            devs, model_parallel=tp),
        **mp_kwargs)
    trainer.init_or_restore()
    hist = trainer.run(args.steps)
    largs.finish_observability(args, "train")
    print(f"[train] {args.arch}: loss {hist[0]:.4f} -> {hist[-1]:.4f}, "
          f"recoveries={trainer.recoveries}")


if __name__ == "__main__":
    main()
