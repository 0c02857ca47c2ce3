"""End-to-end federated fine-tuning simulation (Plato-equivalent).

Reproduces the paper's experiment grid: a pre-trained frozen backbone,
K clients with Dirichlet non-IID shards of a classification task, LoRA
local training (adapters + task head, as in Hu et al.'s GLUE setup), and
one of the aggregation strategies per round.

``run_experiment`` is a thin driver over the unified
:class:`~repro.fed.session.FedSession` API: it stands up the data, the
cohort trainer and the eval function, then hands control to a
:class:`~repro.fed.schedulers.Scheduler` (``SyncRound`` by default —
golden-tested to reproduce the pre-refactor loop bit-for-bit; pass
``scheduler=SemiSync(...)`` / ``BufferedAsync(...)`` for the other
modes). It returns a history {round, train_loss, eval_acc, eval_loss,
downlink_bytes, uplink_bytes, ...} that benchmarks/bench_convergence.py
turns into Fig. 3 / Table 1.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.seeds import derive_seed
from repro.data import (client_batches, dirichlet_partition,
                        make_pair_classification)
from repro.fed.client import (join_adapters, make_cohort_train,
                              make_local_train, split_adapters, split_head)
from repro.fed.schedulers import BufferedAsync, Scheduler, SyncRound
from repro.fed.session import FedSession, ServerConfig
from repro.models import model as model_lib
from repro.optim import adamw, apply_updates


@dataclass
class SimConfig:
    task: str = "mrpc"
    num_examples: int = 4096
    eval_examples: int = 1024
    dirichlet_alpha: float = 0.5
    rounds: int = 20
    local_steps: int = 8           # ≈ paper's E=2 local epochs on a shard
    local_batch: int = 16
    lr: float = 3e-4               # paper's LR
    pretrain_steps: int = 150      # full-param backbone pretraining
    pretrain_lr: float = 1e-3
    seed: int = 0


# ---------------------------------------------------------------------------
# Backbone "pretraining" — the paper starts from RoBERTa-large. Offline, we
# stand up a pretrained backbone by full-param training on an IID *mixture*
# of the task family (different seed ⇒ different sentences than the fed
# shards), then freeze it. LoRA then adapts it to the non-IID task.
# ---------------------------------------------------------------------------

_PRETRAIN_STORE: Dict = {}  # backbone cache: same cfg+seed ⇒ same backbone


def pretrain_backbone(cfg: ModelConfig, sim: SimConfig):
    key = (cfg.name, sim.seed, sim.pretrain_steps, sim.pretrain_lr)
    if key in _PRETRAIN_STORE:
        return _PRETRAIN_STORE[key]
    params = model_lib.init_params(jax.random.PRNGKey(sim.seed), cfg)
    if sim.pretrain_steps > 0:
        rng = np.random.default_rng(
            derive_seed(sim.seed, "pretrain-batches"))
        # Pretrain ONLY on the easy lexical-overlap task (qqp stand-in):
        # the federated phase must then genuinely adapt the representation
        # to the harder shuffled/noised tasks — the domain gap that makes
        # LoRA fine-tuning (and its aggregation quality) matter.
        tokens, labels = make_pair_classification(
            "qqp", sim.num_examples, seed=sim.seed + 777,
            vocab_size=cfg.vocab_size)
        opt = adamw(sim.pretrain_lr)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state, batch):
            def loss(p):
                return model_lib.loss_fn(p, batch, cfg, remat=False)[0]
            l, g = jax.value_and_grad(loss)(params)
            upd, opt_state = opt.update(g, opt_state, params)
            return apply_updates(params, upd), opt_state, l

        bs = 64
        for i in range(sim.pretrain_steps):
            picks = rng.integers(0, len(tokens), size=bs)
            batch = {"tokens": jnp.asarray(tokens[picks]),
                     "labels": jnp.asarray(labels[picks])}
            params, opt_state, l = step(params, opt_state, batch)
    _PRETRAIN_STORE[key] = params
    return params


# ---------------------------------------------------------------------------
# Federated experiment
# ---------------------------------------------------------------------------

def make_experiment_setup(cfg: ModelConfig, sim: SimConfig,
                          scfg: ServerConfig, base_params=None):
    """Data + trainer + eval plumbing shared by every scheduler mode.

    Returns ``(session_kwargs, cohort_train, local_train, data_fn,
    client_data_fn, eval_fn)`` — the pieces a Scheduler.run needs."""
    if base_params is None:
        base_params = pretrain_backbone(cfg, sim)
    frozen, _ = split_head(base_params)

    tokens, labels = make_pair_classification(
        sim.task, sim.num_examples, seed=sim.seed, vocab_size=cfg.vocab_size)
    ev_tokens, ev_labels = make_pair_classification(
        sim.task, sim.eval_examples, seed=sim.seed + 10_000,
        vocab_size=cfg.vocab_size)
    ev_batch = {"tokens": jnp.asarray(ev_tokens),
                "labels": jnp.asarray(ev_labels)}

    shards = dirichlet_partition(labels, scfg.num_clients,
                                 sim.dirichlet_alpha, seed=sim.seed)
    opt = adamw(sim.lr)
    cohort_train = make_cohort_train(cfg, opt)
    local_train = jax.jit(make_local_train(cfg, opt))

    @jax.jit
    def _eval(frozen, lora_tree, head):
        params = {**frozen, **head, "lora": lora_tree}
        _, m = model_lib.loss_fn(params, ev_batch, cfg, remat=False)
        return m

    def eval_fn(lora_tree, head):
        # the backbone goes in as an argument: closed over, it would be
        # baked into the executable as a constant
        return _eval(frozen, lora_tree, head)

    def data_fn(cohort, rnd):
        return _stack_client_data(tokens, labels, shards, cohort, sim, rnd)

    rng = np.random.default_rng(
        derive_seed(sim.seed, "async-client-batches"))

    def client_data_fn(cid):          # async mode: one client's batches
        picks = rng.integers(0, len(shards[cid]),
                             size=(sim.local_steps, sim.local_batch))
        idx = shards[cid][picks]
        return {"tokens": jnp.asarray(tokens[idx]),
                "labels": jnp.asarray(labels[idx])}

    session_kwargs = dict(base_params=base_params,
                          client_sizes=[len(s) for s in shards])
    return (session_kwargs, cohort_train, local_train, data_fn,
            client_data_fn, eval_fn)


def run_experiment(
    cfg: ModelConfig,
    sim: SimConfig,
    scfg: ServerConfig,
    base_params=None,
    eval_every: int = 1,
    engine=None,
    strategy=None,
    scheduler: Optional[Scheduler] = None,
    track_comm: bool = True,
) -> Dict[str, List[float]]:
    """One federated experiment = one FedSession + one Scheduler.

    ``strategy`` (an AggregationStrategy or name) defaults to
    ``scfg.strategy``; ``scheduler`` defaults to ``SyncRound()``;
    ``track_comm=False`` skips the wire round-trip (history byte columns
    become 0) for callers that only want the curves. The session
    aggregates with the batched engine (shared process-wide jit cache
    unless the caller passes a dedicated one): round 1 traces, every
    later round replays the compiled whole-tree aggregation.
    """
    (session_kwargs, cohort_train, local_train, data_fn, client_data_fn,
     eval_fn) = make_experiment_setup(cfg, sim, scfg, base_params)
    session = FedSession(cfg, scfg, engine=engine, strategy=strategy,
                         track_comm=track_comm, **session_kwargs)
    sched = scheduler if scheduler is not None else SyncRound()
    if isinstance(sched, BufferedAsync):
        # one sync round ≈ clients_per_round events: honor the caller's
        # eval cadence at the same granularity
        return sched.run(session, local_train, client_data_fn,
                         num_events=sim.rounds * scfg.clients_per_round,
                         eval_fn=eval_fn,
                         eval_every=eval_every * scfg.clients_per_round)
    return sched.run(session, cohort_train, data_fn, sim.rounds,
                     eval_fn=eval_fn, eval_every=eval_every)


def run_centralized(
    cfg: ModelConfig, sim: SimConfig, rank: int = 8,
    steps: Optional[int] = None, base_params=None,
) -> Dict[str, List[float]]:
    """Centralized LoRA fine-tuning — Table 1's upper-bound row."""
    if base_params is None:
        base_params = pretrain_backbone(cfg, sim)
    frozen, head = split_head(base_params)
    lora0 = {t: dict(ad) for t, ad in base_params["lora"].items()}
    for t in lora0:
        lora0[t]["mask"] = jnp.broadcast_to(
            (jnp.arange(cfg.lora.r_max) < rank).astype(jnp.float32),
            lora0[t]["mask"].shape)
    tokens, labels = make_pair_classification(
        sim.task, sim.num_examples, seed=sim.seed, vocab_size=cfg.vocab_size)
    ev_tokens, ev_labels = make_pair_classification(
        sim.task, sim.eval_examples, seed=sim.seed + 10_000,
        vocab_size=cfg.vocab_size)
    ev_batch = {"tokens": jnp.asarray(ev_tokens),
                "labels": jnp.asarray(ev_labels)}
    steps = steps if steps is not None else sim.rounds * sim.local_steps
    opt = adamw(sim.lr)
    local = jax.jit(make_local_train(cfg, opt))
    factors, masks = split_adapters(lora0)
    trainable = {"factors": factors, "head": head}
    rng = np.random.default_rng(sim.seed)
    history = {"round": [], "train_loss": [], "eval_acc": [], "eval_loss": []}

    @jax.jit
    def eval_fn(frozen, trainable):
        params = {**frozen, **trainable["head"],
                  "lora": join_adapters(trainable["factors"], masks)}
        _, m = model_lib.loss_fn(params, ev_batch, cfg, remat=False)
        return m

    chunk = sim.local_steps
    for rnd in range(max(1, steps // chunk)):
        picks = rng.integers(0, len(tokens), size=(chunk, sim.local_batch))
        data = {"tokens": jnp.asarray(tokens[picks]),
                "labels": jnp.asarray(labels[picks])}
        trainable, loss = local(frozen, trainable, masks, data)[:2]
        m = eval_fn(frozen, trainable)
        history["round"].append(rnd)
        history["train_loss"].append(float(loss))
        history["eval_acc"].append(float(m["acc"]))
        history["eval_loss"].append(float(m["loss"]))
    return history


def _stack_client_data(tokens, labels, shards, cohort, sim: SimConfig,
                       rnd: int):
    per = [client_batches(tokens, labels, shards[cid], sim.local_steps,
                          sim.local_batch,
                          seed=sim.seed * 7919 + rnd * 131 + int(cid))
           for cid in cohort]
    return {
        "tokens": jnp.asarray(np.stack([p["tokens"] for p in per])),
        "labels": jnp.asarray(np.stack([p["labels"] for p in per])),
    }


def rounds_to_target(history: Dict[str, List[float]], target: float):
    for rnd, acc in zip(history["round"], history["eval_acc"]):
        if acc >= target:
            return rnd
    return None
