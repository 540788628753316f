"""Production training launcher.

Runs the paper's pipeline end-to-end on whatever mesh is available:
on a TPU fleet this is the 256/512-chip production mesh (multi-host jax
initializes device topology before this module loads); on the CPU container
it runs a reduced config on the host mesh — same code path, same sharding
rules, same checkpoint layout.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
        --mode analog --steps 200 --reduced --ckpt-dir /tmp/run1
"""

from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.analog import AnalogConfig
from repro.data.corpus import MarkovCorpus
from repro.data.synthetic import GenConfig, generate_synthetic
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build
from repro.train.recipes import distill_recipe, pretrain_recipe
from repro.train.train_step import TrainConfig


def main():
    """CLI entry point (see module docstring for usage)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-3.2-1b")
    ap.add_argument("--mode", default="analog",
                    choices=["analog", "qat", "off"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--pretrain-steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU)")
    ap.add_argument("--synthetic-data", action="store_true",
                    help="paper pipeline: sample training data from the "
                         "teacher instead of the corpus")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduce()

    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    rules = shd.default_rules(mesh)

    key = jax.random.PRNGKey(args.seed)
    cfg, params, labels = build(cfg, key)

    with shd.activate(mesh, rules):
        # stage 0: teacher (pretrained on the structured corpus)
        corpus = MarkovCorpus(cfg.vocab_size, seed=args.seed)
        corpus_tokens = corpus.sample(64 * args.batch, args.seq + 1)
        print(f"[launch] pre-training teacher ({args.pretrain_steps} steps)")
        teacher, _ = pretrain_recipe(
            params, labels, cfg, corpus_tokens,
            num_steps=args.pretrain_steps, batch_size=args.batch,
            ckpt_dir=os.path.join(args.ckpt_dir, "teacher")
            if args.ckpt_dir else None, seed=args.seed)

        # stage 1: data (paper Fig. 2a)
        if args.synthetic_data:
            print("[launch] sampling synthetic corpus from teacher")
            tokens = generate_synthetic(teacher, cfg, key, 32 * args.batch,
                                        args.seq + 1, GenConfig())
        else:
            tokens = corpus_tokens

        if args.mode == "off":
            print("[launch] mode=off: teacher only; done")
            return

        # stage 2: HWA distillation (paper Fig. 2b)
        acfg = AnalogConfig(
            mode=args.mode, gamma_weight=0.02, alpha_clip=3.0,
            init_steps=min(500, args.steps // 4))
        tcfg = TrainConfig(peak_lr=5e-4, total_steps=args.steps,
                           kd_temperature=2.0,
                           grad_compression=args.grad_compression)
        print(f"[launch] HWA distillation mode={args.mode} "
              f"({args.steps} steps)")
        student, trainer = distill_recipe(
            teacher, labels, cfg, tokens, acfg=acfg, tcfg=tcfg,
            batch_size=args.batch, num_steps=args.steps,
            ckpt_dir=os.path.join(args.ckpt_dir, "student")
            if args.ckpt_dir else None, seed=args.seed)
        print(f"[launch] final KD loss: {trainer.history[-1]['kd']:.4f}; "
              f"stragglers flagged: {len(trainer.monitor.events)}")


if __name__ == "__main__":
    main()
