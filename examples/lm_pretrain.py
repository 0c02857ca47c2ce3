"""End-to-end LM training driver: train a ~small decoder for a few hundred
steps on synthetic bigram data and watch the loss approach the chain's
entropy — exercises the full train path (scan layers, remat, AdamW,
checkpointing) on any of the assigned architectures.

  PYTHONPATH=src python examples/lm_pretrain.py --arch gemma-2b --steps 200
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.configs import get_reduced
from repro.data import make_bigram_lm
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.optim import adamw, apply_updates, cosine_decay


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    if cfg.arch_type in ("encoder",):
        raise SystemExit("pick a decoder arch")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    data = make_bigram_lm(4096, args.seq, cfg.vocab_size, seed=0)
    opt = adamw(cosine_decay(args.lr, args.steps, warmup_steps=20))
    opt_state = opt.init(params)

    def make_batch(rng):
        picks = rng.integers(0, len(data["tokens"]), size=args.batch)
        b = {"tokens": jnp.asarray(data["tokens"][picks]),
             "labels": jnp.asarray(data["labels"][picks])}
        if cfg.arch_type == "audio":
            b["frames"] = jnp.zeros((args.batch, cfg.encoder_seq,
                                     cfg.d_model))
        return b

    @jax.jit
    def step(params, opt_state, batch):
        def loss(p):
            return model_lib.loss_fn(p, batch, cfg, remat=True, q_chunk=64)[0]
        l, g = jax.value_and_grad(loss)(params)
        upd, opt_state = opt.update(g, opt_state, params)
        return apply_updates(params, upd), opt_state, l

    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.steps):
        params, opt_state, l = step(params, opt_state, make_batch(rng))
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d}: loss={float(l):.4f} "
                  f"({(time.time() - t0):.1f}s)", flush=True)
    if args.ckpt_dir:
        checkpoint.save(args.ckpt_dir, args.steps, params,
                        meta={"arch": cfg.name, "loss": float(l)})
        print("checkpoint saved:", args.ckpt_dir)


if __name__ == "__main__":
    main()
