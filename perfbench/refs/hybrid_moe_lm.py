"""Plain reference of the hybrid Mamba-2 / attention language model with
routed experts that ``fed_instruct_granite`` runs (Granite-4.0-H,
``GraniteMoeHybridForCausalLM``), its loss and LoRA gradients, and the
federated HLoRA round, in straightforward ``jax.numpy`` (float32,
``highest`` matmul precision) and ``numpy`` float64 for the SVD.

It imports nothing of the program. The weights come from
:func:`make_params` (one jitted call from the seed), in the layout the
program reads (bfloat16, as the configuration states); the reference
computes in float32 from those same values. It runs one layer at a time
(forward, then the backward pass layer by layer from the saved layer
inputs), so it fits on the chip beside the program.

Model (one chip's share: the held experts, the vocabulary slice):

    x = embed[tokens] * 12
    per layer:  h = RMSNorm(x)
                h = Mamba2(h)  or  Attn(h)      # Attn: GQA, no RoPE, causal,
                x = x + 0.22 * h                #       scores * 1/128
                h = RMSNorm(x)
                g = top10(h @ W_router);  w = softmax(g.values)  (all 72)
                y = sum_{i in top10, i held} w_i E_i(h) + S(h)
                x = x + 0.22 * y                # E_i, S: (silu(h W1) * h W3) W2
    logits = RMSNorm(x) @ embed.T / 16;  loss = mean NLL over response tokens
    Mamba2(h): [z, xBC, dt] = h @ in_proj
               xBC = silu(causal_depthwise_conv4(xBC) + conv_b);  [x, B, C] = xBC
               dt = softplus(dt + dt_bias);  A = -exp(A_log)
               y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<u<=t} dt_u A) dt_s x_s + D x_t
               y = RMSNorm(y * silu(z)) over all inner channels;  out = y @ out_proj
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)    (HF's weight = 1 + w)
    LoRA(t) = (alpha / r) * (h (A m)) (m B),  m = first r of r_max

The SSD is the quadratic (attention-like) form over the whole sequence,
not the program's chunked scan. The routed experts are computed densely
for every token and weighted by their gate (zero where a token did not
pick the expert); nothing is sorted or grouped. Local training is Adam
(no weight decay) over the LoRA factors; aggregation is the HLoRA round of
``encoder_classifier.py`` (the same float64 SVD).
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from importlib import util as _imp

_spec = _imp.spec_from_file_location(
    "pb_ref_encoder_classifier",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "encoder_classifier.py"))
_enc = _imp.module_from_spec(_spec)
_spec.loader.exec_module(_enc)

#: the HLoRA round on the host, float64 (shared with the encoder cells)
redistribute = _enc.redistribute
aggregate = _enc.aggregate
effective_update = _enc.effective_update
_fp8_operand, _fp8_grad = _enc._fp8_operand, _enc._fp8_grad

ATTN_TARGETS = ("q", "k", "v", "o")
SSM_TARGETS = ("ssm_in", "ssm_out")


# ---------------------------------------------------------------------------
# Sizes and weights
# ---------------------------------------------------------------------------

def dims(c: dict) -> dict:
    d = c["hidden_size"]
    L = c["num_hidden_layers"]
    types = tuple(c["layer_types"][:L])
    di = c["mamba_expand"] * d
    return {"L": L, "types": types, "d": d,
            "h": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
            "dh": d // c["num_attention_heads"], "V": c["vocab_size"],
            "E": c["experts_routed"], "held": c["num_local_experts"],
            "offset": c["experts_offset"], "k": c["num_experts_per_tok"],
            "ff": c["intermediate_size"],
            "sf": c["shared_intermediate_size"], "di": di,
            "nh": c["mamba_n_heads"], "p": c["mamba_d_head"],
            "n": c["mamba_d_state"], "conv": c["mamba_d_conv"],
            "eps": c["rms_norm_eps"], "att": c["attention_multiplier"],
            "emb": float(c["embedding_multiplier"]),
            "res": c["residual_multiplier"],
            "logit_div": float(c["logits_scaling"]),
            "r": c["lora"]["r_max"], "alpha": float(c["lora"]["alpha"]),
            "targets": tuple(c["lora"]["targets"]),
            "Lm": types.count("mamba"), "La": types.count("attention")}


def lora_depth(g: dict, t: str) -> int:
    return g["La"] if t in ATTN_TARGETS else g["Lm"]


def lora_shape(g: dict, t: str):
    d, di, dh = g["d"], g["di"], g["dh"]
    return {"q": (d, g["h"] * dh), "k": (d, g["hkv"] * dh),
            "v": (d, g["hkv"] * dh), "o": (g["h"] * dh, d),
            "ssm_in": (d, 2 * di + 2 * g["n"] + g["nh"]),
            "ssm_out": (di, d)}[t]


def make_params(key, c: dict):
    """The frozen model in bfloat16 (the SSM's A_log, D and dt_bias in
    float32, as the program keeps them) and the initial global adapter in
    float32, from one key, in one jitted call."""
    return _make_params(key, tuple(sorted(
        (k, v) for k, v in dims(c).items())))


@partial(jax.jit, static_argnums=1)
def _make_params(key, dim_items):
    g = dict(dim_items)
    d, V, E, held = g["d"], g["V"], g["E"], g["held"]
    ff, sf, di, nh = g["ff"], g["sf"], g["di"], g["nh"]
    n, r, lm, la = g["n"], g["r"], g["Lm"], g["La"]
    bf = jnp.bfloat16
    names = iter(range(10_000))

    def nrm(shape, std, dtype=bf):
        return (jax.random.normal(jax.random.fold_in(key, next(names)),
                                  shape, jnp.float32) * std).astype(dtype)

    def mat(*shape):
        return nrm(shape, 1.0 / math.sqrt(shape[-2]))

    def moe(L):
        return {"router": mat(L, d, E), "we1": mat(L, held, d, ff),
                "we3": mat(L, held, d, ff), "we2": mat(L, held, ff, d),
                "w1": mat(L, d, sf), "w3": mat(L, d, sf),
                "w2": mat(L, sf, d)}

    conv_ch = di + 2 * n
    # Mamba-2's initialisation: A = 1..H, dt log-uniform in [1e-3, 0.1]
    u = jax.random.uniform(jax.random.fold_in(key, next(names)), (lm, nh))
    dt0 = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    params = {
        "embed": nrm((V, d), 0.02),
        "mamba": {
            "ln1": {"w": nrm((lm, d), 0.1)},
            "ssm": {"in_proj": mat(lm, d, 2 * di + 2 * n + nh),
                    "conv_w": nrm((lm, g["conv"], conv_ch), 0.5),
                    "conv_b": nrm((lm, conv_ch), 0.02),
                    "A_log": jnp.log(jnp.broadcast_to(
                        jnp.arange(1, nh + 1, dtype=jnp.float32), (lm, nh))),
                    "D": 1.0 + nrm((lm, nh), 0.1, jnp.float32),
                    "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                    "ssm_norm": nrm((lm, di), 0.1),
                    "out_proj": mat(lm, di, d)},
            "ln2": {"w": nrm((lm, d), 0.1)},
            "moe": moe(lm)},
        "attention": {
            "ln1": {"w": nrm((la, d), 0.1)},
            "attn": {"wq": mat(la, d, g["h"] * g["dh"]),
                     "wk": mat(la, d, g["hkv"] * g["dh"]),
                     "wv": mat(la, d, g["hkv"] * g["dh"]),
                     "wo": mat(la, g["h"] * g["dh"], d)},
            "ln2": {"w": nrm((la, d), 0.1)},
            "moe": moe(la)},
        "final_norm": {"w": nrm((d,), 0.1)},
    }
    lora = {}
    for t in g["targets"]:
        L, (din, dout) = lora_depth(g, t), lora_shape(g, t)
        lora[t] = {"A": nrm((L, din, r), 1.0 / math.sqrt(din), jnp.float32),
                   "B": jnp.zeros((L, r, dout), jnp.float32),
                   "mask": jnp.ones((L, r), jnp.float32)}
    return params, lora


# ---------------------------------------------------------------------------
# One layer, the head and the loss (float32 from the stored values)
# ---------------------------------------------------------------------------

def _ops(fp8: bool):
    q8 = _fp8_operand if fp8 else (lambda x: x)
    g8 = _fp8_grad if fp8 else (lambda x: x)

    def ein(spec, a, b):
        return g8(jnp.einsum(spec, q8(a), q8(b)))
    return ein


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def _proj(ein, h, w, ad, alpha):
    y = ein("bsi,io->bso", h, w.astype(jnp.float32))
    if ad is None:
        return y
    m = ad["mask"]
    scale = alpha / jnp.maximum(jnp.sum(m), 1.0)
    return y + scale * ein("bsr,ro->bso",
                           ein("bsi,ir->bsr", h, ad["A"] * m[None, :]),
                           ad["B"] * m[:, None])


def _mamba(x, p, ad, g, ein):
    b, s, _ = x.shape
    di, n, nh, P = g["di"], g["n"], g["nh"], g["p"]
    zxbcdt = _proj(ein, x, p["in_proj"], ad.get("ssm_in"), g["alpha"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    w = p["conv_w"].astype(jnp.float32)                  # (W, C)
    W = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + s] * w[i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    xs, bm, cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])              # (b, s, nh)
    a = -jnp.exp(p["A_log"])                             # (nh,)
    cum = jnp.cumsum(dt * a, axis=1)                     # (b, s, nh)
    t_ge_s = jnp.tril(jnp.ones((s, s), bool))
    seg = cum[:, :, None, :] - cum[:, None, :, :]        # (b, t, s, nh)
    decay = jnp.exp(jnp.where(t_ge_s[None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cm, bm)
    xh = xs.reshape(b, s, nh, P)
    y = jnp.einsum("btsh,bshp->bthp",
                   cb[..., None] * decay * dt[:, None, :, :], xh)
    y = y + p["D"][None, None, :, None] * xh
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    y = _rms(y, p["ssm_norm"], g["eps"])
    return _proj(ein, y, p["out_proj"], ad.get("ssm_out"), g["alpha"])


def _attention(x, p, ad, g, ein):
    b, s, _ = x.shape
    h, hkv, dh = g["h"], g["hkv"], g["dh"]
    q = _proj(ein, x, p["wq"], ad.get("q"), g["alpha"]).reshape(b, s, h, dh)
    k = _proj(ein, x, p["wk"], ad.get("k"), g["alpha"]).reshape(b, s, hkv, dh)
    v = _proj(ein, x, p["wv"], ad.get("v"), g["alpha"]).reshape(b, s, hkv, dh)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    sc = ein("bqhd,bkhd->bhqk", q, k) * g["att"]
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return _proj(ein, o.reshape(b, s, h * dh), p["wo"], ad.get("o"),
                 g["alpha"])


def _moe(x, p, g, ein):
    """The held experts densely over every token, each weighted by its
    gate (0 where the token's top-k missed it), plus the shared expert."""
    logits = ein("bsd,de->bse", x, p["router"].astype(jnp.float32))
    top_v, top_i = jax.lax.top_k(logits, g["k"])
    gates = jax.nn.softmax(top_v, -1)
    held = g["offset"] + jnp.arange(g["held"])
    wgt = jnp.sum(jnp.where(top_i[..., None, :] == held[:, None], gates[
        ..., None, :], 0.0), -1)                          # (b, s, held)

    def swiglu(w1, w3, w2, spec_in, spec_out):
        u = jax.nn.silu(ein(spec_in, x, w1.astype(jnp.float32))) \
            * ein(spec_in, x, w3.astype(jnp.float32))
        return ein(spec_out, u, w2.astype(jnp.float32))
    ye = swiglu(p["we1"], p["we3"], p["we2"], "bsd,edf->bsef",
                "bsef,efd->bsed")
    ys = swiglu(p["w1"], p["w3"], p["w2"], "bsd,df->bsf", "bsf,fd->bsd")
    return jnp.einsum("bse,bsed->bsd", wgt, ye) + ys


def _layer(kind, x, stack, i, ad, g, ein):
    lp = jax.tree.map(lambda a: a[i], stack)
    mix = _mamba if kind == "mamba" else _attention
    h = mix(_rms(x, lp["ln1"]["w"], g["eps"]),
            lp["ssm" if kind == "mamba" else "attn"], ad, g, ein)
    x = x + g["res"] * h
    return x + g["res"] * _moe(_rms(x, lp["ln2"]["w"], g["eps"]),
                               lp["moe"], g, ein)


@partial(jax.jit, static_argnames=("kind", "gi", "fp8", "precision"))
def layer_vjp(x, stack, i, ad, dy, *, kind, gi, fp8, precision):
    """One layer at input ``x``: (its output, (dx, d adapter factors) for
    the output cotangent ``dy``). One program serves both passes: the
    forward pass reads the output, the backward pass the gradients."""
    g = dict(gi)
    masks = {t: a["mask"] for t, a in ad.items()}

    def f(x, fac):
        full = {t: {**fac[t], "mask": masks[t]} for t in fac}
        return _layer(kind, x, stack, i, full, g, _ops(fp8))
    fac = {t: {"A": a["A"], "B": a["B"]} for t, a in ad.items()}
    with jax.default_matmul_precision(precision):
        y, pull = jax.vjp(f, x, fac)
        return y, pull(dy)


@partial(jax.jit, static_argnames=("gi", "fp8", "precision"))
def head_loss(x, final_w, embed, labels, *, gi, fp8, precision):
    """(loss, d loss / d x): next-token NLL over the positions whose
    label is >= 0 (the response), logits over the vocabulary slice."""
    g = dict(gi)
    ein = _ops(fp8)

    def f(x):
        h = _rms(x, final_w, g["eps"])
        logits = ein("bsd,vd->bsv", h, embed.astype(jnp.float32)) \
            / g["logit_div"]
        logp = jax.nn.log_softmax(logits, -1)
        safe = jnp.maximum(labels, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], -1)[..., 0]
        m = (labels >= 0).astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    with jax.default_matmul_precision(precision):
        return jax.value_and_grad(f)(x)


@partial(jax.jit, static_argnames=("gi",))
def embed_tokens(embed, tokens, *, gi):
    return embed[tokens].astype(jnp.float32) * dict(gi)["emb"]


def _layer_index(types):
    """Each layer's (kind, index in that kind's stack)."""
    seen = {"mamba": 0, "attention": 0}
    out = []
    for kind in types:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def forward_logits(params, lora, tokens, *, gi, precision="highest"):
    """(B, S, V) logits of the whole model, one layer at a time."""
    g = dict(gi)
    kw = {"gi": gi, "fp8": False, "precision": precision}
    x = embed_tokens(params["embed"], tokens, gi=gi)
    for kind, i in _layer_index(g["types"]):
        mine = ATTN_TARGETS if kind == "attention" else SSM_TARGETS
        ad = {t: {f: v[i] for f, v in a.items()}
              for t, a in lora.items() if t in mine}
        x = layer_vjp(x, params[kind], i, ad, jnp.zeros_like(x),
                      kind=kind, **kw)[0]
    with jax.default_matmul_precision(precision):
        h = _rms(x, params["final_norm"]["w"], g["eps"])
        return jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(
            jnp.float32)) / g["logit_div"]


def loss_and_grads(params, fac, masks, tokens, labels, *, gi, fp8=False,
                   precision="highest"):
    """Loss and the LoRA factors' gradients of one batch, one layer at a
    time: the forward pass keeps each layer's input, the backward pass
    differentiates each layer again from it."""
    g = dict(gi)
    kw = {"gi": gi, "fp8": fp8, "precision": precision}
    order = _layer_index(g["types"])

    def adapters(kind, i):
        mine = ATTN_TARGETS if kind == "attention" else SSM_TARGETS
        return {t: {"A": fac[t]["A"][i], "B": fac[t]["B"][i],
                    "mask": masks[t][i]} for t in fac if t in mine}
    x = embed_tokens(params["embed"], tokens, gi=gi)
    inputs = []
    for kind, i in order:
        inputs.append(x)
        x = layer_vjp(x, params[kind], i, adapters(kind, i),
                      jnp.zeros_like(x), kind=kind, **kw)[0]
    loss, dx = head_loss(x, params["final_norm"]["w"], params["embed"],
                         labels, **kw)
    grads = {t: {"A": [None] * fac[t]["A"].shape[0],
                 "B": [None] * fac[t]["B"].shape[0]} for t in fac}
    for (kind, i), x in zip(reversed(order), reversed(inputs)):
        _, (dx, dfac) = layer_vjp(x, params[kind], i, adapters(kind, i),
                                  dx, kind=kind, **kw)
        for t, gf in dfac.items():
            grads[t]["A"][i], grads[t]["B"][i] = gf["A"], gf["B"]
    return loss, {t: {f: jnp.stack(v) for f, v in gf.items()}
                  for t, gf in grads.items()}


# ---------------------------------------------------------------------------
# One client's local training: Adam over (A, B)
# ---------------------------------------------------------------------------

def local_train(params, lora, tokens, labels, lr, *, gi, fp8=False,
                precision="highest"):
    """tokens, labels (steps, B, S). Returns the trained factors, each
    step's loss and each leaf's mean gradient norm over the steps
    ({t: {"A"/"B": (L,)}}, {} for the head the model does not train).
    ``precision`` is the matmul precision (``default``: one bfloat16 pass
    on the TPU)."""
    masks = {t: ad["mask"] for t, ad in lora.items()}
    fac = {t: {"A": jnp.asarray(ad["A"]), "B": jnp.asarray(ad["B"])}
           for t, ad in lora.items()}
    b1, b2, eps = 0.9, 0.999, 1e-8
    mu = jax.tree.map(jnp.zeros_like, fac)
    nu = jax.tree.map(jnp.zeros_like, fac)
    losses, gnorms = [], []
    for step in range(tokens.shape[0]):
        loss, gr = loss_and_grads(params, fac, masks, tokens[step],
                                  labels[step], gi=gi, fp8=fp8,
                                  precision=precision)
        t = step + 1
        mu = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, mu, gr)
        nu = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, nu,
                          gr)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        fac = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * ((m_ / c1)
                                          / (jnp.sqrt(v_ / c2) + eps)),
            fac, mu, nu)
        losses.append(float(loss))
        gnorms.append(jax.tree.map(
            lambda v: np.sqrt(np.sum(np.asarray(v, np.float64) ** 2,
                                     axis=tuple(range(1, v.ndim)))), gr))
    gmean = jax.tree.map(lambda *v: np.mean(np.stack(v), 0), *gnorms)
    return fac, {}, np.asarray(losses), (gmean, {})
