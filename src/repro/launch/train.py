"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b --smoke \
        --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Local runs use the reduced smoke config on the host devices; the full
configs are sized for the production mesh (use ``repro.launch.dryrun`` to
validate those without hardware).  Data is the synthetic corpus matching
the arch's modality (see repro.data.synthetic).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore, save
from repro.config import TrainConfig, get_config
from repro.data.synthetic import MarkovLM, MaskedFrames
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models import seq2seq as S
from repro.optim import optimizer_init


def data_for(cfg, batch: int, seq: int, seed: int):
    if cfg.is_encoder_decoder:
        from repro.data.synthetic import PhraseMT

        task = PhraseMT(vocab=cfg.vocab_size, expand=2, seed=seed)
        return task.batches(batch=batch, src_len=max(seq // 2, 4), seed=seed)
    if cfg.modality == "audio":
        task = MaskedFrames(d_model=cfg.d_model,
                            codebook=min(cfg.vocab_size, 504), seed=seed)
        return task.batches(batch=batch, seq_len=seq, seed=seed)
    task = MarkovLM(vocab=min(cfg.vocab_size, 256), temperature=0.2,
                    seed=seed)
    gen = task.batches(batch=batch, seq_len=seq, seed=seed)
    if cfg.modality == "vision_text":
        def with_patches():
            for b in gen:
                b["patch_embeds"] = np.zeros((batch, 4, cfg.d_model),
                                             np.float32)
                yield b
        return with_patches()
    return gen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke).replace(dtype="float32")
    tc = TrainConfig(global_batch=args.batch, seq_len=args.seq, lr=args.lr,
                     steps=args.steps, warmup_steps=max(args.steps // 10, 10),
                     head_loss="random" if cfg.bpd_enabled else "mean")
    init_fn = S.init if cfg.is_encoder_decoder else M.init
    params = init_fn(jax.random.PRNGKey(args.seed), cfg)
    opt = optimizer_init(params, tc)
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        start = latest_step(args.ckpt_dir)
        params, extra = restore(args.ckpt_dir, params)
        print(f"[train] restored step {start} from {args.ckpt_dir}")

    step_fn = jax.jit(steps_lib.make_train_step(cfg, tc))
    gen = data_for(cfg, args.batch, args.seq, args.seed + 1)
    key = jax.random.PRNGKey(args.seed + 2)
    t0 = time.time()
    for i in range(start, args.steps):
        key, sub = jax.random.split(key)
        batch = {k: jnp.asarray(v) for k, v in next(gen).items()}
        params, opt, metrics = step_fn(params, opt, batch, sub)
        if (i + 1) % args.log_every == 0:
            rate = (i + 1 - start) * args.batch * args.seq / (time.time() - t0)
            print(f"[train] step {i + 1:5d}  "
                  f"loss {float(metrics['loss']):.4f}  "
                  f"acc {float(metrics.get('accuracy', 0)):.3f}  "
                  f"{rate:,.0f} tok/s", flush=True)
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            save(args.ckpt_dir, i + 1, params, extra={"arch": args.arch})
    if args.ckpt_dir:
        save(args.ckpt_dir, args.steps, params, extra={"arch": args.arch})
        print(f"[train] final checkpoint -> {args.ckpt_dir}")


if __name__ == "__main__":
    main()
