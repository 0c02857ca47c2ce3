"""Plain reference of the decoder language model the serving cells run:
a full causal forward pass of one sequence with one request's adapter,
in float32 at ``highest`` matmul precision, layer by layer so that it
fits beside the bf16 weights. It imports nothing of the program; the
weights and adapters come from :func:`make_params` and
:func:`make_adapters` (each one jitted call from the seed, in bf16, the
type they are served in), and both sides get the same arrays.

Block, as configured (Phi-3-mini): RMSNorm (weight stored as an offset
from 1), rotate-half RoPE at ``rope_theta`` on every head, full
multi-head causal attention, SwiGLU MLP ``(silu(h W1) * (h W3)) W2``,
untied output head. LoRA on q, k, v, o: ``(alpha / r) (h A) B``.

``fp8=True`` computes the same pass with both operands of every matrix
product rounded to float8 e4m3, one scale per tensor (the control: the
next precision below bf16).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

TARGETS = ("q", "k", "v", "o")


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": d, "h": h,
            "hkv": c["num_key_value_heads"], "dh": d // h,
            "ff": c["intermediate_size"], "V": c["vocab_size"],
            "theta": float(c["rope_theta"]),
            "eps": float(c["rms_norm_eps"]),
            "alpha": float(c["lora"]["alpha"])}


def make_params(key, c: dict):
    """Backbone weights in bf16 from one key, in one jitted call."""
    return _make_params(key, tuple(sorted(dims(c).items())))


@partial(jax.jit, static_argnums=1)
def _make_params(key, gi):
    g = dict(gi)
    L, d, h, hkv, dh, ff, V = (g[k] for k in
                               ("L", "d", "h", "hkv", "dh", "ff", "V"))
    bf = jnp.bfloat16

    def nrm(k, i, shape, std):
        x = jax.random.normal(jax.random.fold_in(k, i), shape, bf)
        return x * jnp.asarray(std, bf)

    def mat(k, i, *shape):
        return nrm(k, i, shape, 1.0 / math.sqrt(shape[-2]))

    def layer(l):
        # one layer at a time, so no more than a layer's random bits
        # are live beside the weights
        k = jax.random.fold_in(key, 1000 + l)
        return {
            "ln1": {"w": nrm(k, 0, (d,), 0.1)},
            "attn": {"wq": mat(k, 1, d, h * dh), "wk": mat(k, 2, d, hkv * dh),
                     "wv": mat(k, 3, d, hkv * dh), "wo": mat(k, 4, h * dh, d)},
            "ln2": {"w": nrm(k, 5, (d,), 0.1)},
            "mlp": {"w1": mat(k, 6, d, ff), "w3": mat(k, 7, d, ff),
                    "w2": mat(k, 8, ff, d)},
        }

    return {
        "embed": nrm(key, 0, (V, d), 0.02),
        "layers": lax.map(layer, jnp.arange(L)),
        "final_norm": {"w": nrm(key, 1, (d,), 0.1)},
        "lm_head": mat(key, 2, d, V),
    }


def make_adapters(key, c: dict, ranks):
    """One bf16 adapter per rank in ``ranks``: {t: {"A": (L, d_in, r),
    "B": (L, r, d_out), "mask": (L, r)}}. B's scale gives every adapter
    an update about a tenth of the base projection's."""
    g = dims(c)
    out = []
    for i, r in enumerate(ranks):
        out.append(_make_adapter(jax.random.fold_in(key, i), int(r),
                                 tuple(sorted(g.items()))))
    return out


@partial(jax.jit, static_argnums=(1, 2))
def _make_adapter(key, r, gi):
    g = dict(gi)
    L, d, h, hkv, dh = (g[k] for k in ("L", "d", "h", "hkv", "dh"))
    shapes = {"q": (d, h * dh), "k": (d, hkv * dh), "v": (d, hkv * dh),
              "o": (h * dh, d)}
    std_b = 0.1 * math.sqrt(r) / g["alpha"]
    tree = {}
    for i, t in enumerate(TARGETS):
        din, dout = shapes[t]
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        tree[t] = {
            "A": (jax.random.normal(ka, (L, din, r), jnp.float32)
                  / math.sqrt(din)).astype(jnp.bfloat16),
            "B": (jax.random.normal(kb, (L, r, dout), jnp.float32)
                  * std_b).astype(jnp.bfloat16),
            "mask": jnp.ones((L, r), jnp.bfloat16)}
    return tree


def _fp8(x):
    """Round to float8 e4m3 with one scale for the tensor, back to f32."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    s, h, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("gi", "fp8"))
def logits(params, adapter, tokens, *, gi, fp8=False):
    """tokens (S,) int32 -> logits (S, V) f32, causal, one adapter."""
    g = dict(gi)
    h, hkv, dh, eps, alpha = g["h"], g["hkv"], g["dh"], g["eps"], g["alpha"]
    s = tokens.shape[0]
    f32 = lambda w: w.astype(jnp.float32)
    q = _fp8 if fp8 else (lambda x: x)

    def mm(a, b):
        return q(a) @ q(f32(b))

    causal = jnp.tril(jnp.ones((s, s), bool))

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])

        def lora(t, hin, ad):
            a = f32(ad[t]["A"]) * f32(ad[t]["mask"])[None, :]
            b = f32(ad[t]["B"]) * f32(ad[t]["mask"])[:, None]
            r = jnp.maximum(jnp.sum(f32(ad[t]["mask"])), 1.0)
            return (alpha / r) * mm(mm(hin, a), b)

        def layer(x, xs):
            lp, ad = xs
            at = lp["attn"]
            hh = _rms(x, lp["ln1"]["w"], eps)
            qh = mm(hh, at["wq"]) + lora("q", hh, ad)
            k = mm(hh, at["wk"]) + lora("k", hh, ad)
            v = mm(hh, at["wv"]) + lora("v", hh, ad)
            qh = _rope(qh.reshape(s, h, dh), g["theta"])
            k = _rope(k.reshape(s, hkv, dh), g["theta"])
            v = v.reshape(s, hkv, dh)
            rep = h // hkv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
            sc = jnp.einsum("qhd,khd->hqk", q(qh), q(k)) / math.sqrt(dh)
            sc = jnp.where(causal[None], sc, -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(sc, -1)), q(v))
            o = o.reshape(s, h * dh)
            x = x + mm(o, at["wo"]) + lora("o", o, ad)
            h2 = _rms(x, lp["ln2"]["w"], eps)
            mp = lp["mlp"]
            u = jax.nn.silu(mm(h2, mp["w1"])) * mm(h2, mp["w3"])
            return x + mm(u, mp["w2"]), None

        x, _ = lax.scan(layer, x, (params["layers"], adapter))
        x = _rms(x, params["final_norm"]["w"], eps)
        return mm(x, params["lm_head"])
