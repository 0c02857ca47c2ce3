"""Mixture-of-Experts FFN with grouped dense dispatch (TPU-native).

Token dispatch uses the einsum/one-hot formulation (Shazeer/MaxText style):
tokens are reshaped into groups of ``moe_group_size``; per group each token
is routed to top-k experts with capacity ``c = g·k·cf / E``. Dispatch and
combine are dense matmuls — no gather/scatter — so the MXU does the routing
and GSPMD shards experts over the 'model' axis (expert parallelism).

Group size is the memory/imbalance knob: the (G, g·k, E, c) dispatch tensor
scales ∝ tokens · g · k · cf (olmoe uses 256, llama4 1024), so a group of
1024 tokens at top-8 over 64 experts already holds ~10M entries per group.

``moe_ffn`` returns (y, aux_loss) with the switch-transformer load-balance
loss. ``routed_moe`` is the dropless expert layer of expert parallelism
(hybrid_moe): it routes over all experts, keeps the (token, expert) pairs
whose expert this device holds, sorts them by expert and runs the held
experts as one grouped matmul (``jax.lax.ragged_dot``); no pair is
dropped.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.configs.base import ModelConfig
from repro.core.lora import Adapter, apply_lora


def moe_ffn(
    x: jax.Array,                       # (B, S, d)
    p: Dict[str, jax.Array],
    cfg: ModelConfig,
    adapters: Optional[Dict[str, Adapter]] = None,
) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    e = cfg.num_experts
    k = cfg.experts_per_token
    t = b * s
    g = min(cfg.moe_group_size, t)
    assert t % g == 0, f"tokens {t} not divisible by group size {g}"
    n_groups = t // g
    cap = max(1, int(math.ceil(g * k * cfg.moe_capacity_factor / e)))

    xg = x.reshape(n_groups, g, d)
    router_logits = jnp.einsum(
        "gsd,de->gse", xg.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)          # (G, g, E)
    top_p, top_idx = jax.lax.top_k(probs, k)                # (G, g, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    # Load-balance aux loss (Switch): E · Σ_e f_e · P_e
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_idx, e, dtype=jnp.float32), axis=2),
        axis=(0, 1)) / k                                     # (E,)
    aux = e * jnp.sum(me * ce)

    # Capacity assignment: position of each (token, slot) within its expert.
    oh = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)       # (G, g, k, E)
    ohf = oh.reshape(n_groups, g * k, e)
    pos = jnp.sum((jnp.cumsum(ohf, axis=1) - ohf) * ohf, axis=-1)  # (G, g·k)
    keep = (pos < cap) & (jnp.sum(ohf, axis=-1) > 0)
    gates = top_p.reshape(n_groups, g * k) * keep
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=x.dtype) \
        * keep[..., None].astype(x.dtype)

    disp = ohf.astype(x.dtype)[..., :, None] * pos_oh[..., None, :]  # (G,gk,E,c)
    xk = jnp.repeat(xg, k, axis=1)                                   # (G, g·k, d)
    xe = jnp.einsum("gtec,gtd->egcd", disp, xk)                      # (E,G,c,d)
    from repro.models import shard_hints
    # EP×DP anchor (§Perf): pays off when the dispatch tensor is large
    # (train/prefill); at decode token counts it costs an extra expert
    # gather, so gate on volume.
    anchor_moe = t > 4096
    if anchor_moe:
        xe = shard_hints.constrain_expert_major(xe)

    # Per-expert gated FFN (experts stacked on the sharded leading axis).
    h = jnp.einsum("egcd,edf->egcf", xe, p["we1"])
    h = jax.nn.silu(h) * jnp.einsum("egcd,edf->egcf", xe, p["we3"])
    ye = jnp.einsum("egcf,efd->egcd", h, p["we2"])                   # (E,G,c,d)
    if anchor_moe:
        ye = shard_hints.constrain_expert_major(ye)

    combine = disp * gates[..., None, None].astype(x.dtype)
    y = jnp.einsum("gtec,egcd->gtd", combine, ye)                    # (G, g·k, d)
    y = y.reshape(n_groups, g, k, d).sum(axis=2)
    y = y.reshape(b, s, d)

    if cfg.moe_shared:  # llama4: always-on shared expert (dense path)
        ad = adapters or {}
        hs = jax.nn.silu(apply_lora(x, p["w1"], ad.get("w1"), cfg.lora.alpha))
        hs = hs * apply_lora(x, p["w3"], ad.get("w3"), cfg.lora.alpha)
        y = y + apply_lora(hs, p["w2"], ad.get("w2"), cfg.lora.alpha)
    return y, aux.astype(jnp.float32)


def init_moe_params(key, cfg: ModelConfig, num_layers: int, dtype):
    """Stacked (L, ...) MoE FFN params."""
    d = cfg.d_model
    e = cfg.num_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 7)
    std_d, std_f = 1.0 / math.sqrt(d), 1.0 / math.sqrt(ff)
    p = {
        "router": (jax.random.normal(ks[0], (num_layers, d, e)) * std_d).astype(dtype),
        "we1": (jax.random.normal(ks[1], (num_layers, e, d, ff)) * std_d).astype(dtype),
        "we3": (jax.random.normal(ks[2], (num_layers, e, d, ff)) * std_d).astype(dtype),
        "we2": (jax.random.normal(ks[3], (num_layers, e, ff, d)) * std_f).astype(dtype),
    }
    if cfg.moe_shared:
        sf = cfg.d_ff
        p["w1"] = (jax.random.normal(ks[4], (num_layers, d, sf)) * std_d).astype(dtype)
        p["w3"] = (jax.random.normal(ks[5], (num_layers, d, sf)) * std_d).astype(dtype)
        p["w2"] = (jax.random.normal(ks[6], (num_layers, sf, d)) * (1 / math.sqrt(sf))).astype(dtype)
    return p


# ---------------------------------------------------------------------------
# Dropless routed experts held by this device (expert parallelism)
# ---------------------------------------------------------------------------

def _route(logits: jax.Array, k: int):
    """Granite routing: the top-k logits, then a softmax over those k."""
    top_v, top_i = jax.lax.top_k(logits, k)
    return jax.nn.softmax(top_v, axis=-1), top_i


def _held_pairs(top_i: jax.Array, offset: int, held: int) -> jax.Array:
    """The pairs this device computes: each (token, slot) pair's local
    expert, or ``held`` where the pair is not computed here. Every pair
    routed to a held expert is computed (dropless). (T, k) -> (T, k)."""
    local = top_i - offset
    return jnp.where((local >= 0) & (local < held), local, held)


def _experts_flat(h, logits, w1, w3, w2, offset: int, k: int):
    """The held experts' part of the layer over flat tokens.

    h (T, d), logits (T, E) f32 -> (y (T, d) f32, kept (T,) f32: pairs
    combined per token). Pairs are sorted by held expert; the worst case
    of T · min(k, held) rows holds every pair a token can send here, so
    none is dropped (the rows past the pairs are masked to zero)."""
    t, d = h.shape
    held = w1.shape[0]
    with jax.named_scope("moe.route"):
        gates, top_i = _route(logits, k)
        key = _held_pairs(top_i, offset, held).reshape(-1)
        gates = gates.reshape(-1)
        n = t * min(k, held)
        order = jnp.argsort(key, stable=True)[:n]
        keep = key[order] < held
        sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                        axis=0)
        tok = order // k
    with jax.named_scope("moe.experts"):
        gs = sizes[:held]
        # a grouped matmul leaves the rows past its groups unwritten on the
        # TPU: zero them at every product, forward and (through the select)
        # backward, so no stale value reaches a token
        rows = (jnp.arange(n) < jnp.sum(gs))[:, None]

        def gmm(a, w):
            return jnp.where(rows, jax.lax.ragged_dot(a, w, gs), 0)
        xs = jnp.where(rows, h[tok], 0)
        u = jax.nn.silu(gmm(xs, w1)) * gmm(xs, w3)
        o = gmm(u.astype(h.dtype), w2)
        wgt = jnp.where(keep, gates[order], 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[tok].add(
            o * wgt[:, None].astype(o.dtype))
        kept = jnp.zeros((t,), jnp.float32).at[tok].add(
            keep.astype(jnp.float32))
    return y, kept


def _token_flat(fn, n_tok: int):
    """``fn(*tok_args, *weights)`` over flat tokens, made batchable: under
    vmap the batch is folded into the token axis (every token is routed
    on its own), so a vmapped cohort shares one grouped matmul.
    ``n_tok`` leading arguments are per token; the rest are weights."""
    @custom_batching.custom_vmap
    def f(*args):
        return fn(*args)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        if any(in_batched[n_tok:]):
            raise NotImplementedError("held experts differ per batch item")
        toks = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
                for a, b in zip(args[:n_tok], in_batched)]
        lead = toks[0].shape[1]
        flat = [a.reshape(axis_size * lead, *a.shape[2:]) for a in toks]
        out = f(*flat, *args[n_tok:])
        out = jax.tree.map(
            lambda o: o.reshape(axis_size, lead, *o.shape[1:]), out)
        return out, jax.tree.map(lambda _: True, out)
    return f


def _held_call(h, logits, w1, w3, w2, offset, k):
    return _token_flat(partial(_experts_flat, offset=offset, k=k),
                       2)(h, logits, w1, w3, w2)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def held_experts(h, logits, w1, w3, w2, offset: int, k: int):
    """:func:`_experts_flat` with a hand-made VJP whose forward and
    backward fold a vmapped cohort into one grouped matmul (``ragged_dot``
    has no batching rule). The frozen expert weights get no gradient."""
    return _held_call(h, logits, w1, w3, w2, offset, k)


def _held_fwd(h, logits, w1, w3, w2, offset, k):
    out = _held_call(h, logits, w1, w3, w2, offset, k)
    return out, (h, logits, w1, w3, w2)


def _held_bwd(offset, k, res, cts):
    h, logits, w1, w3, w2 = res
    dy, _ = cts

    def vjp(h, logits, dy, w1, w3, w2):
        _, pull = jax.vjp(
            lambda a, b: _experts_flat(a, b, w1, w3, w2, offset, k)[0],
            h, logits)
        return pull(dy)
    dh, dlogits = _token_flat(vjp, 3)(h, logits, dy, w1, w3, w2)
    return (dh, dlogits, jnp.zeros_like(w1), jnp.zeros_like(w3),
            jnp.zeros_like(w2))


held_experts.defvjp(_held_fwd, _held_bwd)


def routed_moe(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B, S, d) -> (y, stats): the held routed experts' part of a
    Granite MoE plus its shared expert,

        g = top-k(x W_router) over all ``num_experts``;  w = softmax(g)
        y = sum_{i in top-k, i held} w_i E_i(x) + S(x),
        E_i, S = (silu(x W1) * x W3) W2.

    ``stats``: ``load`` (held,) pairs the router sent to each held
    expert, ``dropped`` () pairs sent here and not combined (0: dropless).
    """
    b, s, d = x.shape
    k, held = cfg.experts_per_token, cfg.num_held_experts
    offset = cfg.moe_expert_offset
    h = x.reshape(b * s, d)
    with jax.named_scope("moe.route"):
        logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        _, top_i = _route(jax.lax.stop_gradient(logits), k)
        # what the router sent here, counted apart from the pairs
        # _experts_flat computes, so a pair it leaves out shows as dropped
        local = top_i.reshape(-1) - offset
        sent = (local >= 0) & (local < held)
        load = jnp.sum(jax.nn.one_hot(jnp.where(sent, local, held), held,
                                      dtype=jnp.float32), axis=0)
    y, kept = held_experts(h, logits, p["we1"], p["we3"], p["we2"],
                           offset, k)
    stats = {"load": load,
             "dropped": jnp.sum(sent.astype(jnp.float32)) - jnp.sum(kept)}
    hs = jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])
    y = y + (hs @ p["w2"]).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, s, d), stats


def init_routed_params(key, cfg: ModelConfig, num_layers: int, dtype):
    """Stacked (L, ...) router over all experts, the held experts and
    the shared expert."""
    d, e, held = cfg.d_model, cfg.num_experts, cfg.num_held_experts
    ff, sf = cfg.moe_d_ff, cfg.d_ff
    ks = jax.random.split(key, 7)

    def nrm(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)
    return {
        "router": nrm(ks[0], (num_layers, d, e), d),
        "we1": nrm(ks[1], (num_layers, held, d, ff), d),
        "we3": nrm(ks[2], (num_layers, held, d, ff), d),
        "we2": nrm(ks[3], (num_layers, held, ff, d), ff),
        "w1": nrm(ks[4], (num_layers, d, sf), d),
        "w3": nrm(ks[5], (num_layers, d, sf), d),
        "w2": nrm(ks[6], (num_layers, sf, d), sf),
    }
