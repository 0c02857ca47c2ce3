"""The hybrid Mamba-2 / attention model with routed experts (hybrid_moe,
Granite-4.0-H) on the training path: the dropless expert layer that holds
a share of the experts, its routing statistics, and LoRA targets that
live on different subsets of layers through the wire and the merge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import agg_engine
from repro.fed.client import make_cohort_train
from repro.fed.schedulers import SemiSync, SyncRound
from repro.fed.session import FedSession, ServerConfig
from repro.models import model as model_lib
from repro.models import moe
from repro.models import transformer as tf_lib
from repro.optim import adamw

CFG = get_reduced("granite-4.0-h-small")


def _moe_params(cfg, key=0):
    p = moe.init_routed_params(jax.random.PRNGKey(key), cfg, 1, jnp.float32)
    return jax.tree.map(lambda a: a[0], p)


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _dense_layer(x, p, cfg, experts):
    """Every expert in ``experts`` computed for every token, weighted by
    its gate (0 where the token's top-k missed it), plus the shared one."""
    h = x.reshape(-1, cfg.d_model)
    v, i = jax.lax.top_k(h @ p["router"], cfg.experts_per_token)
    g = jax.nn.softmax(v, -1)
    y = _swiglu(h, p["w1"], p["w3"], p["w2"])
    for j, e in enumerate(experts):
        ge = jnp.sum(jnp.where(i == e, g, 0.0), -1)
        y = y + ge[:, None] * _swiglu(h, p["we1"][j], p["we3"][j],
                                      p["we2"][j])
    return y.reshape(x.shape)


def test_expert_shares_add_up_to_the_whole_layer():
    """Four devices of 2 experts each: their parts, with the shared
    expert counted once, add up to the layer that holds all 8."""
    p = _moe_params(CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, CFG.d_model))
    held, e = 2, CFG.num_experts
    shared = _swiglu(x, p["w1"], p["w3"], p["w2"])
    total = -(e // held - 1) * shared
    for off in range(0, e, held):
        cfg = CFG.with_(moe_experts_held=held, moe_expert_offset=off)
        part = dict(p, **{w: p[w][off:off + held]
                          for w in ("we1", "we3", "we2")})
        y, st = moe.routed_moe(x, part, cfg)
        np.testing.assert_allclose(
            y, _dense_layer(x, part, cfg, range(off, off + held)),
            rtol=1e-5, atol=1e-5)
        assert float(st["dropped"]) == 0.0
        total = total + y
    whole, st = moe.routed_moe(x, p, CFG)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(whole, _dense_layer(x, p, CFG, range(e)),
                               rtol=1e-5, atol=1e-5)
    assert float(st["load"].sum()) == 2 * 16 * CFG.experts_per_token


def _imbalanced(p):
    """A router that scores every expert alike: top-k breaks the ties by
    the lowest index, so every token goes to experts 0..k-1."""
    return dict(p, router=jnp.zeros_like(p["router"]))


def test_dropless_under_forced_imbalance():
    """Every token picks the same experts: each of them gets every token,
    all are computed, none is dropped."""
    p = _imbalanced(_moe_params(CFG))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, CFG.d_model))
    y, st = jax.jit(lambda x: moe.routed_moe(x, p, CFG))(x)
    k = CFG.experts_per_token
    np.testing.assert_array_equal(st["load"][:k], 32.0)
    assert float(st["dropped"]) == 0.0
    np.testing.assert_allclose(y, _dense_layer(x, p, CFG,
                                               range(CFG.num_experts)),
                               rtol=1e-5, atol=1e-5)


def test_vmapped_grads_match_per_client():
    """The cohort shares one grouped matmul under vmap; each client's
    output and input gradient equal its own unbatched call."""
    p = _moe_params(CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 1, 8, CFG.d_model))

    def f(x):
        return jnp.sum(moe.routed_moe(x, p, CFG)[0] ** 2)
    g = jax.vmap(jax.grad(f))(x)
    for c in range(3):
        np.testing.assert_allclose(g[c], jax.grad(f)(x[c]), rtol=1e-5,
                                   atol=1e-5)


def _batches(cohort, rnd, s=16):
    toks = jax.random.randint(jax.random.PRNGKey(rnd),
                              (len(cohort), 2, 1, s), 0, CFG.vocab_size)
    labels = jnp.where(jnp.arange(s) >= s // 2, jnp.roll(toks, -1, -1), -1)
    return {"tokens": toks, "labels": labels}


@pytest.mark.parametrize("sched", [SyncRound(),
                                   SemiSync(speeds=np.ones(4), deadline=1e9)],
                         ids=["sync", "semisync"])
def test_session_counts_routing_and_no_drop(sched):
    """A round through FedSession and a scheduler with the trainer's
    routing statistics: the counters see every pair this device computed,
    none dropped, even with every token sent to the same experts."""
    params = model_lib.init_params(jax.random.PRNGKey(0), CFG)
    for kind in ("mamba", "attention"):
        params[kind]["moe"] = _imbalanced(params[kind]["moe"])
    sess = FedSession(CFG, ServerConfig(num_clients=4, clients_per_round=2,
                                        r_min=2, r_max=CFG.lora.r_max),
                      params)
    trainer = make_cohort_train(CFG, adamw(1e-3))
    sched.run(sess, trainer, _batches, 1)
    m = sess.metrics
    pairs = 2 * 2 * 16 * CFG.num_layers * CFG.experts_per_token
    assert m.counter("fed.moe_routed").value == pairs
    assert m.counter("fed.moe_dropped").value == 0
    # experts 0..k-1 take every token, the others none
    k, e = CFG.experts_per_token, CFG.num_experts
    assert m.gauge("fed.moe_load_max_over_mean").value == pytest.approx(
        e / k)


def test_lora_targets_live_on_their_layers():
    depth = tf_lib.lora_depths(CFG)
    assert depth == {"q": 1, "k": 1, "v": 1, "o": 1, "ssm_in": 2,
                     "ssm_out": 2}
    lora = tf_lib.init_lora(jax.random.PRNGKey(0), CFG)
    assert lora["ssm_in"]["A"].shape == (2, CFG.d_model,
                                         CFG.lora.r_max)
    assert lora["q"]["B"].shape == (1, CFG.lora.r_max, CFG.d_model)


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_unequal_depths_through_wire_and_merge(codec):
    """Targets of depth 1 and 2 round-trip the cohort wire path and merge
    in one engine call exactly as each target merged alone."""
    params = model_lib.init_params(jax.random.PRNGKey(0), CFG)
    scfg = ServerConfig(num_clients=5, clients_per_round=3, r_min=2,
                        r_max=CFG.lora.r_max, codec=codec)
    sess = FedSession(CFG, scfg, params)
    cohort = np.array([0, 2, 4])
    stacked, _ = sess.broadcast_cohort(cohort)
    key = jax.random.PRNGKey(7)
    trained = {}
    for i, (t, ad) in enumerate(sorted(stacked.items())):
        noise = jax.random.normal(jax.random.fold_in(key, i), ad["B"].shape)
        trained[t] = dict(ad, B=ad["B"] + 0.1 * noise * ad["mask"][
            ..., :, None])
    tree, _ = sess.collect_updates(cohort, trained, None)
    for t in trained:
        assert tree[t]["A"].shape == trained[t]["A"].shape
    eta = sess.cohort_weights(cohort)
    eng = agg_engine.AggregationEngine()
    full = {t: jnp.ones_like(ad["mask"][:1]) for t, ad in tree.items()}
    together, _ = eng(tree, eta, CFG.lora.alpha, new_masks=full)
    for t in tree:
        alone, _ = eng({t: tree[t]}, eta, CFG.lora.alpha,
                       new_masks={t: full[t]})
        for leaf in ("A", "B", "mask"):
            np.testing.assert_allclose(together[t][leaf], alone[t][leaf],
                                       rtol=1e-5, atol=1e-6)


def test_rows_a_grouped_matmul_leaves_unwritten_reach_no_token(
        monkeypatch):
    """On the TPU the grouped matmul leaves the rows past its groups
    unwritten. Filled with NaN here, forward and backward, they must not
    reach an output or a gradient."""
    real = jax.lax.ragged_dot

    def nan_tail(a, gs, out):
        rows = jnp.arange(out.shape[0]) < jnp.sum(gs)
        return jnp.where(rows[:, None], out, jnp.nan)

    @jax.custom_vjp
    def ragged(a, w, gs):
        return nan_tail(a, gs, real(a, w, gs))

    def fwd(a, w, gs):
        return ragged(a, w, gs), (a, w, gs)

    def bwd(res, ct):
        a, w, gs = res
        _, pull = jax.vjp(lambda a: real(a, w, gs), a)
        return (nan_tail(a, gs, pull(ct)[0]), jnp.zeros_like(w),
                np.zeros(gs.shape, jax.dtypes.float0))
    ragged.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged)
    cfg = CFG.with_(moe_experts_held=4)
    p = _moe_params(cfg)
    p = dict(p, **{w: p[w][:4] for w in ("we1", "we3", "we2")})
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 1, 16, cfg.d_model))

    def f(x):
        return moe.routed_moe(x, p, cfg)[0]
    y = jax.vmap(f)(x)
    g = jax.vmap(jax.grad(lambda x: jnp.sum(f(x) ** 2)))(x)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(g).all())
    np.testing.assert_allclose(
        y[0], _dense_layer(x[0], p, cfg, range(4)), rtol=1e-5, atol=1e-5)
