"""LoRA adapters with static-shape heterogeneous ranks (HLoRA building block).

Conventions
-----------
We use the row-vector convention ``y = x @ W`` with ``W: (d_in, d_out)``.
The paper (column convention, ``W ∈ R^{d×k}``, ``ΔW = B A``) maps onto ours
by transposition:

    paper A (r×k, input-side, gaussian init)  ->  ours ``A`` (d_in, r_max)
    paper B (d×r, output-side, zero init)     ->  ours ``B`` (r_max, d_out)
    ΔW_ours = A @ B   ( = (B_paper A_paper)^T )

Heterogeneous ranks with static shapes
--------------------------------------
jit requires static shapes, and federated client-parallelism wants one
pytree structure for *all* clients. Every adapter is therefore allocated at
``r_max`` and carries a binary ``mask: (r_max,)`` with ``mask[i] = 1`` iff
``i < r_k``. Masked rank directions contribute exactly zero to
``ΔW = (A·mask) @ B``, so the semantics are identical to truly
variable-rank LoRA, while client trees stack/vmap/shard_map cleanly.

The LoRA scale is ``alpha / r_eff`` where ``r_eff = sum(mask)`` — each
client's scaling matches what standalone LoRA at its rank would use.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Adapter = Dict[str, jax.Array]  # {"A", "B", "mask"}


def make_rank_mask(rank, r_max: int, dtype=jnp.float32) -> jax.Array:
    """mask[i] = 1. iff i < rank. ``rank`` may be a traced scalar."""
    return (jnp.arange(r_max) < rank).astype(dtype)


def init_adapter(
    key: jax.Array,
    d_in: int,
    d_out: int,
    r_max: int,
    rank: Optional[int] = None,
    stack_dims: Tuple[int, ...] = (),
    dtype=jnp.float32,
) -> Adapter:
    """Create one adapter. ``stack_dims`` prepends leading axes (e.g. layers).

    Init follows Hu et al.: input-side factor gaussian (std 1/sqrt(d_in)),
    output-side factor zero, so ΔW = 0 at t=0.
    """
    rank = r_max if rank is None else rank
    a = jax.random.normal(key, (*stack_dims, d_in, r_max), dtype) / jnp.sqrt(d_in)
    b = jnp.zeros((*stack_dims, r_max, d_out), dtype)
    mask = jnp.broadcast_to(make_rank_mask(rank, r_max, dtype), (*stack_dims, r_max))
    return {"A": a, "B": b, "mask": mask}


def effective_rank(adapter: Adapter) -> jax.Array:
    """Per-stack-entry effective rank (sum of mask over the last axis)."""
    return jnp.sum(adapter["mask"], axis=-1)


def lora_scale(adapter: Adapter, alpha: float) -> jax.Array:
    r_eff = jnp.maximum(effective_rank(adapter), 1.0)
    return alpha / r_eff


def masked_factors(adapter: Adapter) -> Tuple[jax.Array, jax.Array]:
    """(A·mask, B·mask). Masking either factor suffices for ΔW; masking both
    also kills gradient flow into dead rank directions (so a client can never
    'train through' a rank it was not assigned)."""
    m = adapter["mask"]
    a = adapter["A"] * m[..., None, :]
    b = adapter["B"] * m[..., :, None]
    return a, b


def delta_w(adapter: Adapter, alpha: float) -> jax.Array:
    """The effective weight update ΔW = scale · (A·m) @ (B·m)."""
    a, b = masked_factors(adapter)
    scale = lora_scale(adapter, alpha)
    return scale[..., None, None] * jnp.einsum("...ir,...ro->...io", a, b)


def apply_lora(
    x: jax.Array, w0: jax.Array, adapter: Optional[Adapter], alpha: float,
    scale_override: Optional[jax.Array] = None,
) -> jax.Array:
    """y = x @ W0 + scale · (x @ A·m) @ (B·m).

    ``w0`` is the frozen base matrix. The adapter path computes in
    **x.dtype** (adapters keep f32 master copies; they are cast per use).
    Upcasting x to f32 here contaminates the whole backward pass with f32
    activation cotangents — the dominant collective volume of the
    sharded train step.
    """
    y = x @ w0
    if adapter is None:
        return y
    a, b = masked_factors(adapter)
    scale = scale_override if scale_override is not None else lora_scale(adapter, alpha)
    xa = jnp.einsum("...si,...ir->...sr", x, a.astype(x.dtype))
    lo = jnp.einsum("...sr,...ro->...so", xa, b.astype(x.dtype))
    sc = jnp.asarray(scale, lo.dtype)
    if sc.ndim:
        sc = sc[..., None, None]
    return y + (sc * lo).astype(y.dtype)


def merge(w0: jax.Array, adapter: Adapter, alpha: float) -> jax.Array:
    """Fold the adapter into the base weights (deployment path)."""
    return w0 + delta_w(adapter, alpha).astype(w0.dtype)


def adapter_num_params(adapter: Adapter) -> int:
    return adapter["A"].size + adapter["B"].size


def comm_bytes(adapter: Adapter, rank: Optional[int] = None) -> int:
    """Bytes a client actually transmits per round. With rank masks the
    zeroed directions need not cross the wire: only r_k of r_max columns
    are sent (this is what makes HLoRA communication ∝ r_k, claim C4)."""
    a, b = adapter["A"], adapter["B"]
    r_max = a.shape[-1]
    r = r_max if rank is None else rank
    d_in, d_out = a.shape[-2], b.shape[-1]
    stack = 1
    for s in a.shape[:-2]:
        stack *= s
    itemsize = a.dtype.itemsize
    return stack * (d_in * r + r * d_out) * itemsize


def tree_init(
    key: jax.Array,
    specs: Dict[str, Tuple[int, int]],
    r_max: int,
    rank: Optional[int] = None,
    stack_dims_map: Optional[Dict[str, Tuple[int, ...]]] = None,
    dtype=jnp.float32,
) -> Dict[str, Adapter]:
    """Init a dict of adapters from {target: (d_in, d_out)} specs."""
    keys = jax.random.split(key, len(specs))
    out = {}
    for k, (name, (d_in, d_out)) in zip(keys, sorted(specs.items())):
        stack = (stack_dims_map or {}).get(name, ())
        out[name] = init_adapter(k, d_in, d_out, r_max, rank, stack, dtype)
    return out
