"""Production training launcher: ``--arch <id>`` selects an assigned
architecture (reduced config by default on this CPU container; the full
config is for real pods and is exercised via dryrun.py).

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-350m \
        --steps 20 [--full] [--ckpt DIR]
"""
from __future__ import annotations

import argparse

from repro.configs import registry
from repro.data.pipeline import DataConfig
from repro.ft.failure import FailureInjector
from repro.optim.optimizer import OptConfig
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--micro-batch", type=int, default=2)
    ap.add_argument("--accum", type=int, default=4)
    ap.add_argument("--full", action="store_true",
                    help="use the full (pod-scale) config — needs real HW")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--inject-failure", action="store_true")
    ap.add_argument("--chunk-units", type=int, default=1,
                    help="micro-batches per stealable chunk")
    ap.add_argument("--no-steal", action="store_true",
                    help="disable intra-step work stealing")
    args = ap.parse_args(argv)
    from repro.core import compile_cache
    compile_cache.enable()

    cfg = registry.get(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder or cfg.frontend != "none":
        raise SystemExit(f"{args.arch}: use launch.serve / custom driver "
                         "for non-token-LM archs")
    print(f"training {cfg.name} ({'full' if args.full else 'reduced'}): "
          f"{cfg.n_layers}L d={cfg.d_model}")
    inj = (FailureInjector(kill={args.steps // 3: "host"},
                           revive={2 * args.steps // 3: "host"})
           if args.inject_failure else None)
    trainer = Trainer(
        cfg,
        OptConfig(lr=3e-4, warmup_steps=5, total_steps=max(args.steps, 50)),
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   micro_batch=args.micro_batch),
        TrainerConfig(accum_units=args.accum, steps=args.steps,
                      ckpt_dir=args.ckpt,
                      ckpt_every=max(args.steps // 3, 1),
                      chunk_units=args.chunk_units,
                      steal=not args.no_steal,
                      time_model=lambda g, k: k * (
                          0.001 if g == "accel" else 0.004)),
        injector=inj)
    out = trainer.run()
    h = out["history"]
    print(f"done: loss {h[0].loss:.4f} -> {h[-1].loss:.4f}")


if __name__ == "__main__":
    main()
