"""Production training driver.

    python -m repro.launch.train --arch yi-9b --policy shiftadd --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1

On a real TPU slice this binary is what every host runs (jax.distributed
initialization is environment-driven); on CPU, --reduced configs train for
real. The loop is fault-tolerant: checkpoint/restart + deterministic data
replay; rerunning the same command after a crash resumes.
"""
from __future__ import annotations

import argparse

import jax

from repro.checkpoint import Checkpointer
from repro.configs.base import TrainConfig
from repro.configs.registry import get_config, list_archs
from repro.data.pipeline import SyntheticLMData
from repro.distributed import sharding as shard_lib
from repro.nn.model import LanguageModel
from repro.train import train_loop
from repro.utils.logging import get_logger

log = get_logger("repro.launch.train")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--policy", default="dense",
                    choices=["dense", "shiftadd", "stage1", "all_shift"])
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x4 → (data=2, model=4) over local devices")
    args = ap.parse_args()

    cfg = get_config(args.arch, policy=args.policy, reduced=args.reduced)
    cfg = cfg.replace(moe_primitives_capacity=2.0)
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=min(20, args.steps // 5),
                       total_steps=args.steps, global_batch=args.batch,
                       seq_len=args.seq, microbatch=args.microbatch,
                       grad_compression=args.grad_compression,
                       checkpoint_every=max(10, args.steps // 10))

    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[: len(shape)]
        mesh = shard_lib.make_mesh(shape, axes)
        shard_lib.set_active_mesh(mesh)

    model = LanguageModel(cfg)
    data = SyntheticLMData(cfg.vocab_size, args.seq, args.batch, seed=tcfg.seed,
                           input_mode=cfg.input_mode, d_model=cfg.d_model,
                           mrope=(cfg.rope == "mrope"))
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None

    def hook(m):
        if m["step"] % 20 == 0:
            log.info("step %5d  loss %.4f  ce %.4f  %.2fs",
                     m["step"], m["loss"], m.get("ce", float("nan")),
                     m["seconds"])

    if mesh is not None:
        with mesh:
            state, hist = train_loop(model, tcfg, data, mesh=mesh,
                                     checkpointer=ckpt, metrics_hook=hook)
    else:
        state, hist = train_loop(model, tcfg, data, checkpointer=ckpt,
                                 metrics_hook=hook)
    log.info("done: loss %.4f -> %.4f over %d steps",
             hist[0]["loss"], hist[-1]["loss"], len(hist))


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
