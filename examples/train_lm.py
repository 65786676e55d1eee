"""End-to-end training driver: train a ~100M-param granite-family model for a
few hundred steps on the synthetic token stream, with checkpointing and
crash-resume.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import store
from repro.data.lm_data import TokenStream
from repro.models import api
from repro.models.api import ModelConfig
from repro.optim import adamw
from repro.runtime import compile_cache
from repro.train import loop as tl


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full-100m", action="store_true",
                    help="train the ~100M config (use on real accelerators; "
                    "the default is a ~10M config sized for 1 CPU core)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    if args.full_100m:  # ~100M params: granite-family (llama-style)
        cfg = ModelConfig(
            name="granite-100m", family="dense",
            n_layers=8, d_model=512, n_heads=8, n_kv_heads=4, head_dim=64,
            d_ff=1536, vocab=4096, mlp="swiglu", q_chunk=128, loss_chunk=128,
            microbatches=2,
        )
    else:  # ~10M: same family, sized for the CPU-only container
        cfg = ModelConfig(
            name="granite-10m", family="dense",
            n_layers=4, d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
            d_ff=768, vocab=1024, mlp="swiglu", q_chunk=64, loss_chunk=64,
        )
    model = api.build_model(cfg)
    print(f"model: {cfg.name}  params={model.n_params/1e6:.1f}M")

    opt_cfg = adamw.AdamWConfig(
        peak_lr=3e-4, warmup_steps=20, total_steps=args.steps, weight_decay=0.01
    )
    params = model.init(jax.random.PRNGKey(0))
    state = adamw.init(params, opt_cfg)
    start = 0

    restored, at = store.restore_latest({"params": params, "opt": state}, args.ckpt_dir)
    if restored is not None:
        params, state, start = restored["params"], restored["opt"], at
        print(f"resumed from checkpoint at step {at}")

    step_fn = jax.jit(tl.make_train_step(model, opt_cfg), donate_argnums=(0, 1))
    stream = TokenStream(cfg.vocab, seed=0)

    t0 = time.time()
    for i, batch in enumerate(
        stream.batches(args.steps - start, args.batch, args.seq), start=start
    ):
        params, state, m = step_fn(params, state, {"tokens": jnp.asarray(batch["tokens"])})
        if i % 20 == 0 or i == args.steps - 1:
            print(
                f"step {i:4d}  loss={float(m['loss']):.4f}  "
                f"gnorm={float(m['grad_norm']):.3f}  lr={float(m['lr']):.2e}  "
                f"({(time.time()-t0):.1f}s)"
            )
        if (i + 1) % args.ckpt_every == 0:
            store.save({"params": params, "opt": state}, i + 1, args.ckpt_dir)
            print(f"checkpointed step {i+1}")
    print("done. final loss:", float(m["loss"]))


if __name__ == "__main__":
    main()
