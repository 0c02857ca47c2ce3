"""Optional in-model sharding constraints (§Perf optimization O2).

GSPMD propagates the 2D weight sharding P(fsdp, 'model') through the
(B,S,H·Dh) -> (B,S,H,Dh) reshape. When H doesn't divide the model axis
(gemma: 8 heads on 16 chips) the propagated sharding SPLITS head_dim, so
the attention contraction over Dh produces partial sums — an all-reduce
of the full (B,H,Sq,Skv) logits every layer (309 GB/device for gemma
train_4k). Constraining q/k/v to head-aligned shardings replaces that
with one cheap activation reshard.

Disabled by default; ``enable()`` turns it on for the optimized
variant. Requires a mesh context at trace time.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

_STATE = {"on": False, "batch": None, "model": "model", "model_size": 1,
          "batch_size": 1}


def enable(batch_axes, model_axis: str, model_size: int,
           batch_size: int) -> None:
    _STATE.update(on=True, batch=batch_axes, model=model_axis,
                  model_size=model_size, batch_size=batch_size)


def disable() -> None:
    _STATE["on"] = False


def enabled() -> bool:
    return _STATE["on"]


def constrain_heads(x: jax.Array, batch: int) -> jax.Array:
    """x: (B, S, H, Dh). Shard H over the model axis when divisible,
    otherwise leave heads replicated (never split Dh)."""
    if not _STATE["on"]:
        return x
    h = x.shape[2]
    baxis = _STATE["batch"] if batch % _STATE["batch_size"] == 0 else None
    maxis = _STATE["model"] if h % _STATE["model_size"] == 0 else None
    return jax.lax.with_sharding_constraint(x, P(baxis, None, maxis, None))


def constrain_tokens(x: jax.Array, batch: int) -> jax.Array:
    """x: (B, S, D) residual activations: batch-sharded, D replicated."""
    if not _STATE["on"]:
        return x
    baxis = _STATE["batch"] if batch % _STATE["batch_size"] == 0 else None
    return jax.lax.with_sharding_constraint(x, P(baxis, None, None))


def constrain_expert_major(x: jax.Array) -> jax.Array:
    """x: (E, G, c, d) MoE dispatched tokens: experts over 'model', groups
    over the data axes, capacity/d local. Anchoring this stops GSPMD from
    all-gathering the (G, g·k, E, c) dispatch tensor across the data axis
    (measured 2×343 GB/device/step on olmoe prefill — §Perf pair C')."""
    if not _STATE["on"]:
        return x
    e, g = x.shape[0], x.shape[1]
    eaxis = _STATE["model"] if e % _STATE["model_size"] == 0 else None
    gaxis = _STATE["batch"] if g % _STATE["batch_size"] == 0 else None
    return jax.lax.with_sharding_constraint(x, P(eaxis, gaxis, None, None))
