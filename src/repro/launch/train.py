"""Production training launcher.

On a real TPU pod slice this runs under `jax.distributed.initialize()` with
one process per host; here it drives the same code path on the local
device set.  Fault tolerance comes from the supervised restart loop
(`repro.runtime.trainer`); elastic rescale from the offset-based
checkpoints.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b \
        --reduced --steps 50 --model-parallel 2
"""
from __future__ import annotations

import argparse

import jax

from ..configs import get_config, get_reduced
from ..data.pipeline import DataConfig, SyntheticTokens
from ..models import LM, ShardCtx
from ..runtime.trainer import Trainer, TrainerConfig, run_supervised
from .compile_cache import enable_compile_cache
from .mesh import data_axes_of, make_host_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--deadline-s", type=float, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_host_mesh(args.model_parallel) \
        if args.model_parallel > 1 else None
    shard = ShardCtx(mesh=mesh, data_axes=data_axes_of(mesh)) if mesh \
        else ShardCtx()
    data = SyntheticTokens(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.global_batch, modality=cfg.modality,
        d_model=cfg.d_model, enc_seq=args.seq))
    tcfg = TrainerConfig(total_steps=args.steps,
                         ckpt_every=max(args.steps // 4, 1),
                         ckpt_dir=args.ckpt_dir,
                         grad_compression=args.compress,
                         step_deadline_s=args.deadline_s)

    out = run_supervised(lambda: Trainer(LM(cfg, shard), data, tcfg),
                         jax.random.PRNGKey(0))
    print(f"done: step={out['final_step']} restarts={out['restarts']} "
          f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")


if __name__ == "__main__":
    main()
