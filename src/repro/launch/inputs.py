"""ShapeDtypeStruct stand-ins for every model input — no device allocation.

``input_specs(cfg, shape)`` returns the abstract batch for the shape's
kind; ``abstract_state`` builds abstract params / optimizer state / caches
via jax.eval_shape. ``config_for`` applies the per-shape architecture
variants (sliding-window for dense long-context decode) and ``skip_reason``
names the (architecture, shape) pairs that have no step to build.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig, get_config
from repro.models import model as model_lib

LONG_WINDOW = 4096  # sliding-window variant for dense archs at long_500k


def config_for(arch: str, shape: InputShape) -> Tuple[ModelConfig, str]:
    """Returns (cfg, variant_note)."""
    cfg = get_config(arch)
    note = ""
    if (shape.kind == "decode" and shape.seq_len > 100_000
            and cfg.arch_type in ("dense", "moe", "vlm")
            and cfg.sliding_window is None):
        cfg = cfg.with_(sliding_window=LONG_WINDOW)
        note = f"sliding-window({LONG_WINDOW}) variant"
    return cfg, note


def skip_reason(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.kind == "decode" and not cfg.supports_decode:
        return "encoder-only: no decode step"
    if (shape.kind == "decode" and shape.seq_len > 100_000
            and cfg.arch_type == "audio"):
        return "whisper: full-attention enc-dec, 30s-audio domain"
    return None


def input_specs(cfg: ModelConfig, shape: InputShape,
                dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    """The abstract data batch for train/prefill; token for decode."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.num_classes:
            batch["labels"] = jax.ShapeDtypeStruct((b,), i32)
        else:
            batch["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        if cfg.arch_type == "audio":
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), dtype)
        return batch
    return {"token": jax.ShapeDtypeStruct((b, 1), i32),
            "pos": jax.ShapeDtypeStruct((), i32)}


def abstract_params(cfg: ModelConfig, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg, dtype))


def abstract_cache(cfg: ModelConfig, shape: InputShape, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: model_lib.init_cache(cfg, shape.global_batch, shape.seq_len,
                                     dtype))
