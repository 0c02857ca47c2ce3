"""Plain reference of the encoder classifier the federated cells run, and
its federated HLoRA round, in straightforward ``jax.numpy`` (float32,
``highest`` matmul precision) and ``numpy`` float64 for the SVD.

It imports nothing of the program. The weights come from
:func:`make_params` (one jitted call from the seed), in the layout the
program reads, and both sides get the same tree.

Model, as the program runs it (the configuration file lists where that
departs from published RoBERTa-large):

    x   = E[tokens] * sqrt(d) + sinusoid(positions)
    per layer (pre-norm):
        h = LN1(x);  q,k,v = h W{q,k,v} + b + LoRA(q), LoRA(v)
        x = x + (softmax(q k^T / sqrt(dh)) v) Wo + bo      (no mask)
        x = x + gelu_tanh(LN2(x) W1 + b1) W2 + b2
    logits = LN_f(x)[:, 0] Wc + bc;  loss = mean NLL

    LoRA(t) = (alpha / r) * (h (A m)) (m B),  m = first r of r_max

Local training is Adam (no weight decay) over the LoRA factors and the
classification head; aggregation is the HLoRA round: the weighted sum of
the clients' effective updates, its top-r_max SVD (A' = U, B' = S V^T,
B' scaled by r_max/alpha), and each client's rank-r_k truncation with B
scaled by r_k/r_max.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def dims(c: dict) -> dict:
    d = c["hidden_size"]
    return {"L": c["num_hidden_layers"], "d": d,
            "h": c["num_attention_heads"],
            "dh": d // c["num_attention_heads"],
            "ff": c["intermediate_size"], "V": c["vocab_size"],
            "C": c["num_labels"], "r": c["lora"]["r_max"],
            "alpha": float(c["lora"]["alpha"]),
            "targets": tuple(c["lora"]["targets"])}


def make_params(key, c: dict):
    """The backbone, the head and the initial global adapter, in f32,
    from one key, in one jitted call."""
    return _make_params(key, tuple(sorted(dims(c).items())))


@partial(jax.jit, static_argnums=1)
def _make_params(key, dim_items):
    g = dict(dim_items)
    L, d, ff, V, C, r = g["L"], g["d"], g["ff"], g["V"], g["C"], g["r"]
    names = iter(range(10_000))

    def nrm(shape, std):
        return jax.random.normal(jax.random.fold_in(key, next(names)),
                                 shape, jnp.float32) * std

    def mat(*shape):
        return nrm(shape, 1.0 / math.sqrt(shape[-2]))

    def ln(*lead):
        return {"w": 1.0 + nrm((*lead, d), 0.1), "b": nrm((*lead, d), 0.02)}

    params = {
        "embed": nrm((V, d), 0.02),
        "layers": {
            "ln1": ln(L),
            "attn": {"wq": mat(L, d, d), "wk": mat(L, d, d),
                     "wv": mat(L, d, d), "wo": mat(L, d, d),
                     "bq": nrm((L, d), 0.02), "bk": nrm((L, d), 0.02),
                     "bv": nrm((L, d), 0.02), "bo": nrm((L, d), 0.02)},
            "ln2": ln(L),
            "mlp": {"w1": mat(L, d, ff), "w2": mat(L, ff, d),
                    "b1": nrm((L, ff), 0.02), "b2": nrm((L, d), 0.02)},
        },
        "final_norm": ln(),
        "cls_head": mat(d, C),
        "cls_bias": nrm((C,), 0.02),
    }
    lora = {t: {"A": mat(L, d, r), "B": jnp.zeros((L, r, d), jnp.float32),
                "mask": jnp.ones((L, r), jnp.float32)}
            for t in g["targets"]}
    return params, lora


# ---------------------------------------------------------------------------
# Forward, loss
# ---------------------------------------------------------------------------

def _ln(x, p, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["w"] + p["b"]


def _sinusoid(s, d):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _round8(x, dtype, top):
    """Round to an 8-bit float with one scale for the whole tensor."""
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _fp8_operand(x):
    """A product's operand in float8 e4m3 (the control's forward)."""
    return x + lax.stop_gradient(_round8(x, jnp.float8_e4m3fn, 448.0) - x)


@jax.custom_vjp
def _fp8_grad(y):
    """Identity; the gradient arriving at a product's output is rounded to
    float8 e5m2 (the control's backward), as fp8 training recipes do."""
    return y


_fp8_grad.defvjp(lambda y: (y, None),
                 lambda _, g: (_round8(g, jnp.float8_e5m2, 57344.0),))


def forward(params, lora, tokens, g: dict, fp8: bool = False):
    """tokens (B, S) -> logits (B, C). ``lora``: {t: {"A","B","mask"}},
    stacked over layers. ``fp8`` computes every matrix product as fp8
    training does (the control): operands in e4m3, the gradient at its
    output in e5m2, each tensor with one scale."""
    p = params
    q8 = _fp8_operand if fp8 else (lambda x: x)
    g8 = _fp8_grad if fp8 else (lambda x: x)

    def mm(a, b):
        return g8(q8(a) @ q8(b))
    b, s = tokens.shape
    d, h, dh = g["d"], g["h"], g["dh"]
    x = p["embed"][tokens] * math.sqrt(d) + _sinusoid(s, d)[None]

    def lora_add(y, hin, ad):
        if ad is None:
            return y
        m = ad["mask"]
        scale = g["alpha"] / jnp.maximum(jnp.sum(m), 1.0)
        return y + scale * mm(mm(hin, ad["A"] * m[None, :]),
                              ad["B"] * m[:, None])

    def layer(x, xs):
        lp, ad = xs
        at = lp["attn"]
        hh = _ln(x, lp["ln1"])
        q = lora_add(mm(hh, at["wq"]), hh, ad.get("q")) + at["bq"]
        k = lora_add(mm(hh, at["wk"]), hh, ad.get("k")) + at["bk"]
        v = lora_add(mm(hh, at["wv"]), hh, ad.get("v")) + at["bv"]
        q, k, v = (t.reshape(b, s, h, dh) for t in (q, k, v))
        logit = g8(jnp.einsum("bqhd,bkhd->bhqk", q8(q), q8(k))) \
            / math.sqrt(dh)
        o = g8(jnp.einsum("bhqk,bkhd->bqhd",
                          q8(jax.nn.softmax(logit, -1)), q8(v)))
        o = o.reshape(b, s, h * dh)
        x = x + lora_add(mm(o, at["wo"]), o, ad.get("o")) + at["bo"]
        h2 = _ln(x, lp["ln2"])
        mp = lp["mlp"]
        u = jax.nn.gelu(mm(h2, mp["w1"]) + mp["b1"], approximate=True)
        return x + mm(u, mp["w2"]) + mp["b2"], None

    x, _ = lax.scan(layer, x, (p["layers"], lora))
    x = _ln(x, p["final_norm"])
    return mm(x[:, 0], p["cls_head"]) + p["cls_bias"]


def loss(params, lora, head, tokens, labels, g, fp8=False):
    logits = forward({**params, **head}, lora, tokens, g, fp8)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[:, None], -1).mean()


# ---------------------------------------------------------------------------
# One client's local training: Adam over (A, B, head)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("gi", "fp8", "precision"))
def local_train(params, lora, head, tokens, labels, lr, *, gi, fp8=False,
                precision="highest"):
    """tokens (steps, B, S), labels (steps, B). Returns the trained
    factors and head, each step's loss, and each leaf's mean gradient
    norm over the steps ({"A"/"B": (T, L), "head": {k: ()}}).
    ``precision`` is the matmul precision (``default``: one bfloat16 pass
    on the TPU, as the program's float32 products run)."""
    g = dict(gi)
    masks = {t: ad["mask"] for t, ad in lora.items()}
    train = ({t: {"A": ad["A"], "B": ad["B"]} for t, ad in lora.items()},
             head)
    b1, b2, eps = 0.9, 0.999, 1e-8

    def lossf(tr, tok, lab):
        fac, hd = tr
        lo = {t: {**fac[t], "mask": masks[t]} for t in fac}
        return loss(params, lo, hd, tok, lab, g, fp8)

    def step(carry, batch):
        tr, mu, nu, t = carry
        with jax.default_matmul_precision(precision):
            l, gr = jax.value_and_grad(lossf)(tr, *batch)
        t = t + 1
        mu = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, mu, gr)
        nu = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, nu, gr)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        tr = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * ((m_ / c1)
                                          / (jnp.sqrt(v_ / c2) + eps)),
            tr, mu, nu)
        fac_g, head_g = gr
        gn = ({t_: {k: jnp.sqrt(jnp.sum(v ** 2, axis=tuple(
            range(1, v.ndim)))) for k, v in f.items()}
            for t_, f in fac_g.items()},
            {k: jnp.linalg.norm(v) for k, v in head_g.items()})
        return (tr, mu, nu, t), (l, gn)

    zeros = jax.tree.map(jnp.zeros_like, train)
    (train, _, _, _), (losses, gnorms) = lax.scan(
        step, (train, zeros, zeros, jnp.float32(0.0)),
        (tokens, labels))
    gmean = jax.tree.map(lambda x: x.mean(0), gnorms)
    return train[0], train[1], losses, gmean


# ---------------------------------------------------------------------------
# The HLoRA round on the host, float64
# ---------------------------------------------------------------------------

def redistribute(global_lora, rank: int, r_max: int):
    """Client k's start: the global masked to its rank, B scaled by
    r_k / r_max (the effective update is the rank-r_k truncation)."""
    m = (np.arange(r_max) < rank).astype(np.float32)
    out = {}
    for t, ad in global_lora.items():
        L = ad["A"].shape[0]
        out[t] = {"A": np.asarray(ad["A"]) * m[None, None, :],
                  "B": np.asarray(ad["B"]) * m[None, :, None]
                  * (rank / r_max),
                  "mask": np.broadcast_to(m, (L, r_max)).copy()}
    return out


def aggregate(trained, ranks, eta, alpha: float, r_max: int):
    """trained: per client {t: {"A","B"}} (numpy); ranks, eta per client.
    Returns the new global {t: {"A","B","mask"}} in float32."""
    eta = np.asarray(eta, np.float64)
    eta = eta / eta.sum()
    out = {}
    for t in trained[0]:
        L = trained[0][t]["A"].shape[0]
        A_new, B_new = [], []
        for l in range(L):
            ps, qs = [], []
            for k, tr in enumerate(trained):
                r = int(ranks[k])
                a = np.asarray(tr[t]["A"][l], np.float64)[:, :r]
                b = np.asarray(tr[t]["B"][l], np.float64)[:r, :]
                ps.append(a * (eta[k] * alpha / r))
                qs.append(b)
            p = np.concatenate(ps, 1)
            q = np.concatenate(qs, 0)
            qp, rp = np.linalg.qr(p)
            qq, rq = np.linalg.qr(q.T)
            uc, s, vct = np.linalg.svd(rp @ rq.T)
            u = qp @ uc[:, :r_max]
            vt = (qq @ vct.T[:, :r_max]).T
            A_new.append(u)
            B_new.append(s[:r_max, None] * vt * (r_max / alpha))
        out[t] = {"A": np.stack(A_new).astype(np.float32),
                  "B": np.stack(B_new).astype(np.float32),
                  "mask": np.ones((L, r_max), np.float32)}
    return out


def effective_update(ad, alpha: float):
    """(L, d_in, d_out) effective update of an adapter, float64."""
    m = np.asarray(ad["mask"], np.float64)
    a = np.asarray(ad["A"], np.float64) * m[:, None, :]
    b = np.asarray(ad["B"], np.float64) * m[:, :, None]
    scale = alpha / np.maximum(m.sum(-1), 1.0)
    return scale[:, None, None] * np.einsum("lir,lro->lio", a, b)
