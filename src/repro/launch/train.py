"""Production training launcher: ``python -m repro.launch.train --arch <id>``.

On a real pod this process runs per host with jax.distributed initialized by
the scheduler; on this CPU container use ``--smoke`` (reduced config, host
mesh) to exercise the identical code path end-to-end.
"""
import argparse
import dataclasses
import logging

import jax

from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig
from repro.launch import sharding as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import data_shards, make_host_mesh, make_production_mesh
from repro.models import RuntimeConfig, build_model
from repro.optim import OptConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on the host mesh")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
        mesh = make_host_mesh()
        rt = RuntimeConfig(remat="none", moe_groups=data_shards(mesh))
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        rt = RuntimeConfig(remat="dots", moe_groups=data_shards(mesh))

    model = build_model(cfg, rt)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch,
                          frontend_tokens=cfg.frontend_tokens,
                          frontend_dim=cfg.d_model,
                          enc_frames=cfg.cross_attention_len
                          if cfg.encoder_decoder else 0)
    trainer = Trainer(model, OptConfig(decay_steps=args.steps), data_cfg,
                      TrainerConfig(total_steps=args.steps,
                                    ckpt_dir=args.ckpt_dir))
    _, _, hist = trainer.run()
    print("final:", hist[-1] if hist else "no metrics")


if __name__ == "__main__":
    main()
