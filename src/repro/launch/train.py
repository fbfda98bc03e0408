"""Training launcher.

Runs real training on whatever devices exist (the CPU container trains the
paper's reduced configs; on a TPU pod the same entry point scales via the
production mesh).  Example:

  PYTHONPATH=src python -m repro.launch.train --arch tconst-41m \\
      --steps 200 --batch 8 --seq 256 --reduced --log-every 10
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.config import get_config, reduced
from repro.data.pipeline import DataConfig, batches
from repro.models.api import build_model
from repro.training.checkpoint import save_train_state
from repro.training.optim import AdamWConfig, init_opt_state
from repro.training.schedules import warmup_cosine, wsd
from repro.training.train_step import make_train_step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tconst-41m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--schedule", choices=["cosine", "wsd"], default="cosine")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (CPU-scale) variant")
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--data", default="synthetic", choices=["synthetic",
                                                            "text"])
    ap.add_argument("--text-path", default="")
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, vocab_size=args.vocab)
    if cfg.attention_mode in ("tconst", "tlin"):
        assert args.seq % cfg.tconst.w_og == 0, \
            f"--seq must be a multiple of W_og={cfg.tconst.w_og}"
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(params))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.2f}M "
          f"mode={cfg.attention_mode}")

    opt_cfg = AdamWConfig(lr=args.lr)
    opt = init_opt_state(params, opt_cfg)
    sched = (wsd(args.steps // 20, int(args.steps * 0.85),
                 args.steps // 10) if args.schedule == "wsd"
             else warmup_cosine(args.steps // 20, args.steps))
    step_fn = jax.jit(make_train_step(api, opt_cfg, sched,
                                      n_micro=args.n_micro),
                      donate_argnums=(0, 1))

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    batch_size=args.batch, seed=args.seed,
                    kind=args.data, text_path=args.text_path)
    t0 = time.time()
    for i, b in enumerate(batches(dc, steps=args.steps)):
        batch = {"tokens": jnp.asarray(b["tokens"][:, :args.seq])}
        if cfg.arch_type == "vlm":
            Tv = cfg.frontend_tokens
            batch["vision_embeds"] = jnp.zeros(
                (args.batch, Tv, cfg.frontend_dim), jnp.dtype(cfg.dtype))
            batch["vision_mask"] = jnp.zeros(
                (args.batch, args.seq), bool).at[:, :Tv].set(True)
        if cfg.is_encdec:
            batch["audio_feats"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.frontend_dim),
                jnp.dtype(cfg.dtype))
        params, opt, m = step_fn(params, opt, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            toks = args.batch * args.seq * (i + 1)
            print(f"[train] step {i:5d} loss={float(m['loss']):.4f} "
                  f"gnorm={float(m['grad_norm']):.3f} "
                  f"tok/s={toks/(time.time()-t0):9.0f}", flush=True)
    if args.ckpt_dir:
        path = save_train_state(params, opt, args.steps, args.ckpt_dir)
        print(f"[train] checkpoint -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
