"""Async federated mode + HLO-parser + shard-hints unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.fed import SimConfig, ServerConfig
from repro.fed.async_server import (AsyncConfig, AsyncFedServer,
                                    simulate_async_rounds)
from repro.fed.client import make_local_train, split_head
from repro.fed.simulation import pretrain_backbone
from repro.models import model as model_lib
from repro.optim import sgd


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("roberta-large")
    sim = SimConfig(num_examples=512, pretrain_steps=40, seed=0)
    base = pretrain_backbone(cfg, sim)
    return cfg, base


def test_async_server_staleness_and_versions(setup):
    cfg, base = setup
    scfg = ServerConfig(num_clients=6, clients_per_round=6,
                        rank_policy="random", r_min=2, r_max=8, seed=0)
    acfg = AsyncConfig(max_staleness=50)
    speeds = np.array([4.0, 2.0, 1.0, 1.0, 0.5, 0.25])
    server = AsyncFedServer(cfg, scfg, acfg, base, speeds)

    from repro.data import make_pair_classification
    tokens, labels = make_pair_classification(
        "qqp", 256, vocab_size=cfg.vocab_size)
    frozen, _ = split_head(base)
    local = jax.jit(make_local_train(cfg, sgd(1e-2)))

    rng = np.random.default_rng(0)

    def data_fn(cid):
        picks = rng.integers(0, len(tokens), size=(2, 8))
        return {"tokens": jnp.asarray(tokens[picks]),
                "labels": jnp.asarray(labels[picks])}

    h = simulate_async_rounds(server, local, frozen, data_fn, num_events=12)
    assert server.version >= 10
    # fast clients go first => early updates have low staleness; slow
    # clients arrive later with higher staleness
    assert max(h["staleness"]) > 0
    assert h["staleness"][0] == 0
    # global adapter moved and stays finite
    for t, ad in server.global_lora.items():
        assert bool(jnp.all(jnp.isfinite(ad["A"])))
        assert bool(jnp.all(jnp.isfinite(ad["B"])))
    # eval still runs on global params
    ev = {"tokens": jnp.asarray(tokens[:64]),
          "labels": jnp.asarray(labels[:64])}
    _, m = model_lib.loss_fn(server.global_params(), ev, cfg, remat=False)
    assert bool(jnp.isfinite(m["loss"]))


def test_async_drops_too_stale(setup):
    cfg, base = setup
    scfg = ServerConfig(num_clients=2, clients_per_round=2, seed=0)
    server = AsyncFedServer(cfg, scfg, AsyncConfig(max_staleness=1), base,
                            [1.0, 1.0])
    ad, ver = server.adapter_for(0)
    server.version = 5  # simulate progress
    assert server.submit(0, ad, ver) is False  # tau=5 > 1 -> dropped


def test_shard_hints_noop_when_disabled():
    from repro.models import shard_hints
    shard_hints.disable()
    x = jnp.ones((2, 4, 8))
    assert shard_hints.constrain_tokens(x, 2) is x
    y = jnp.ones((4, 2, 3, 8))
    assert shard_hints.constrain_expert_major(y) is y
