"""Per-architecture smoke tests (reduced configs) + decode/forward
consistency — including the SSD recurrence vs chunked-scan equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_reduced
from repro.models import model as model_lib

B, S = 2, 32


def _batch(cfg, key):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.arch_type == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(key, 1), (B, cfg.encoder_seq, cfg.d_model))
    batch["labels"] = (jnp.zeros((B,), jnp.int32) if cfg.num_classes
                       else tokens)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch, rng_key):
    """One forward + one LoRA train step on CPU: shapes + finite."""
    cfg = get_reduced(arch)
    params = model_lib.init_params(rng_key, cfg)
    batch = _batch(cfg, rng_key)
    logits, aux = model_lib.forward(params, batch, cfg, q_chunk=16)
    if cfg.num_classes:
        assert logits.shape == (B, cfg.num_classes)
    else:
        assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    # one LoRA-only train step must change the adapters and stay finite
    from repro.fed.client import make_local_train, split_adapters, split_head
    from repro.optim import sgd
    frozen, head = split_head(params)
    factors, masks = split_adapters(params["lora"])
    local = make_local_train(cfg, sgd(1e-2), q_chunk=16)
    data = jax.tree.map(lambda x: x[None], batch)  # 1 step
    trainable = {"factors": factors, "head": head}
    out, loss = local(frozen, trainable, masks, data)[:2]
    assert bool(jnp.isfinite(loss))
    moved = any(
        float(jnp.abs(out["factors"][t]["B"] - factors[t]["B"]).max()) > 0
        for t in factors)
    assert moved, "LoRA B factors did not move"


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if get_reduced(a).supports_decode])
def test_decode_matches_forward(arch, rng_key):
    """Teacher-forced decode equals the parallel forward — validates KV
    caches, ring buffers, conv state, and the SSD recurrence."""
    cfg = get_reduced(arch)
    if cfg.num_experts:
        # capacity-dropping is group-size dependent; decode≡forward only
        # holds when no token is dropped — raise capacity for the check
        cfg = cfg.with_(moe_capacity_factor=8.0)
    params = model_lib.init_params(rng_key, cfg)
    batch = _batch(cfg, rng_key)
    tokens = batch["tokens"]
    logits, _ = model_lib.forward(params, batch, cfg, remat=False, q_chunk=16)

    if cfg.arch_type == "audio":
        from repro.models import whisper as wl
        cache = wl.prefill_cache(params, batch["frames"], cfg, B, S,
                                 jnp.float32)
    else:
        cache = model_lib.init_cache(cfg, B, S, jnp.float32)

    steps = min(S, 12)
    errs = []
    for t in range(steps):
        lg, cache = model_lib.decode_step(
            params, cache, tokens[:, t:t + 1], jnp.int32(t), cfg)
        errs.append(float(jnp.abs(lg - logits[:, t, :]).max()))
    scale = float(jnp.abs(logits[:, :steps]).max())
    assert max(errs) < 2e-3 * max(scale, 1.0), (arch, errs)


def test_ssd_chunk_invariance(rng_key):
    """ssd_chunked must give identical output for any chunk size."""
    from repro.models.mamba2 import ssd_chunked
    b, s, h, p, n = 2, 64, 4, 8, 16
    ks = jax.random.split(rng_key, 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.2)
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(jax.random.fold_in(rng_key, 9), (b, s, n))
    y16, s16 = ssd_chunked(x, dt, a, bm, cm, chunk=16)
    y64, s64 = ssd_chunked(x, dt, a, bm, cm, chunk=64)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s16), np.asarray(s64),
                               rtol=1e-4, atol=1e-4)


def test_sliding_window_limits_context(rng_key):
    """With window w, logits at position t must not depend on tokens
    earlier than t-w+1."""
    cfg = get_reduced("gemma-2b").with_(sliding_window=8)
    params = model_lib.init_params(rng_key, cfg)
    t1 = jax.random.randint(rng_key, (1, 32), 3, cfg.vocab_size)
    t2 = t1.at[0, 0:4].set((t1[0, 0:4] + 5) % cfg.vocab_size)
    l1, _ = model_lib.forward(params, {"tokens": t1}, cfg, remat=False,
                              q_chunk=16)
    l2, _ = model_lib.forward(params, {"tokens": t2}, cfg, remat=False,
                              q_chunk=16)
    # position 31 sees tokens 24..31 only -> unchanged
    np.testing.assert_allclose(np.asarray(l1[0, 31]), np.asarray(l2[0, 31]),
                               rtol=1e-4, atol=1e-4)
    # position 5 does see the change
    assert float(jnp.abs(l1[0, 5] - l2[0, 5]).max()) > 1e-4


def test_moe_router_balance_aux(rng_key):
    cfg = get_reduced("olmoe-1b-7b")
    params = model_lib.init_params(rng_key, cfg)
    batch = _batch(cfg, rng_key)
    _, aux = model_lib.forward(params, batch, cfg, q_chunk=16)
    assert float(aux) > 0.0  # switch loss ≥ 1 per layer in expectation


def test_param_count_sanity():
    from repro.configs import get_config
    # published sizes within tolerance (embeddings included)
    approx = {
        "gemma-2b": 2.5e9, "mamba2-2.7b": 2.7e9, "minitron-4b": 4.2e9,
        "granite-34b": 34e9, "chameleon-34b": 34e9,
        "command-r-plus-104b": 104e9, "olmoe-1b-7b": 6.9e9,
        # the assigned spec (48L × 128 routed experts of d_ff 8192, all
        # layers MoE) totals ~778B; Maverick's published 400B uses
        # interleaved dense layers — we implement the assigned shape.
        "llama4-maverick-400b-a17b": 778e9,
    }
    # active-parameter count must be ~17B (the A17B in the name)
    cfg4 = get_config("llama4-maverick-400b-a17b")
    active = cfg4.active_param_count()
    assert 10e9 < active < 25e9, active
    for name, expect in approx.items():
        got = get_config(name).param_count()
        assert 0.55 * expect < got < 1.45 * expect, (name, got, expect)
