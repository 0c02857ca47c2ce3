"""Client-side local training: LoRA factors only, base frozen.

The local trainer is a jit-compiled scan over minibatches and is *vmapped
over clients* — rank masks give every client the same pytree shapes, so a
whole cohort trains as one batched program (this replaces Plato's
process-per-client simulation; on the production mesh the vmap axis is
sharded over 'data', see launch/train.py).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import model as model_lib
from repro.optim import apply_updates

Factors = Dict[str, Dict[str, jax.Array]]   # {target: {"A","B"}}
Masks = Dict[str, jax.Array]                 # {target: mask}


def split_adapters(lora_tree) -> Tuple[Factors, Masks]:
    factors = {t: {"A": ad["A"], "B": ad["B"]} for t, ad in lora_tree.items()}
    masks = {t: ad["mask"] for t, ad in lora_tree.items()}
    return factors, masks


def join_adapters(factors: Factors, masks: Masks):
    return {t: {"A": f["A"], "B": f["B"], "mask": masks[t]}
            for t, f in factors.items()}


HEAD_KEYS = ("cls_head", "cls_bias")


def split_head(base_params):
    """Classification configs train the task head alongside LoRA (as in
    Hu et al.'s GLUE setup). Returns (frozen_base, head or {})."""
    head = {k: base_params[k] for k in HEAD_KEYS if k in base_params}
    frozen = {k: v for k, v in base_params.items()
              if k not in head and k != "lora"}
    return frozen, head


#: routing statistics a model's loss reports (hybrid_moe)
STAT_KEYS = ("moe_load", "moe_dropped")


def make_local_train(cfg: ModelConfig, opt, remat: bool = False,
                     q_chunk: int = 1024):
    """Returns local_train(frozen_base, trainable, masks, data) ->
    (trainable', mean_loss) with trainable = {"factors", "head"}.
    ``data`` leaves are (steps, batch, ...). A model whose loss reports
    routing statistics (``STAT_KEYS``: hybrid_moe) adds them, summed over
    the steps, as a third output."""

    def loss(trainable, masks, frozen, batch):
        params = {**frozen, **trainable["head"],
                  "lora": join_adapters(trainable["factors"], masks)}
        l, metrics = model_lib.loss_fn(params, batch, cfg, remat=remat,
                                       q_chunk=q_chunk)
        return l, {k: metrics[k] for k in STAT_KEYS if k in metrics}

    def local_train(frozen, trainable, masks, data):
        opt_state = opt.init(trainable)

        def step_fn(carry, batch):
            tr, st = carry
            (l, stats), g = jax.value_and_grad(loss, has_aux=True)(
                tr, masks, frozen, batch)
            upd, st = opt.update(g, st, tr)
            tr = apply_updates(tr, upd)
            return (tr, st), (l, stats)

        (trainable, _), (losses, stats) = lax.scan(
            step_fn, (trainable, opt_state), data)
        if stats:
            return trainable, jnp.mean(losses), jax.tree.map(
                lambda s: jnp.sum(s, axis=0), stats)
        return trainable, jnp.mean(losses)

    return local_train


def make_cohort_train(cfg: ModelConfig, opt, remat: bool = False,
                      q_chunk: int = 1024):
    """vmap the local trainer over a client cohort.

    frozen base broadcast; trainable/masks/data have a leading cohort axis.
    """
    local = make_local_train(cfg, opt, remat, q_chunk)
    return jax.jit(jax.vmap(local, in_axes=(None, 0, 0, 0)))


@partial(jax.jit, static_argnames=("cfg",))
def evaluate(params, batch, cfg: ModelConfig):
    _, metrics = model_lib.loss_fn(params, batch, cfg, remat=False)
    return metrics
