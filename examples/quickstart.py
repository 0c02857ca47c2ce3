"""Quickstart: HLoRA in ~60 lines.

Three clients with different LoRA ranks fine-tune a small model on
non-IID shards; the server reconstructs ΔW = Σ η_k B_k A_k exactly
(Eq. 2) and re-decomposes per client rank via SVD (Eq. 3).

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.data import dirichlet_partition, make_pair_classification
from repro.fed import FedServer, ServerConfig, SimConfig
from repro.fed.client import (join_adapters, make_cohort_train,
                              split_adapters, split_head)
from repro.fed.simulation import _stack_client_data, pretrain_backbone
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.optim import adamw


def main():
    enable_compile_cache()
    cfg = get_reduced("roberta-large")
    sim = SimConfig(task="mrpc", num_examples=1024, rounds=3, local_steps=6,
                    local_batch=16, pretrain_steps=100, lr=1e-3)
    print(f"model: {cfg.name} ({cfg.num_layers}L d={cfg.d_model}) — "
          f"LoRA targets {cfg.lora.targets}, r_max={cfg.lora.r_max}")

    base = pretrain_backbone(cfg, sim)
    frozen, _ = split_head(base)

    tokens, labels = make_pair_classification(
        sim.task, sim.num_examples, vocab_size=cfg.vocab_size)
    shards = dirichlet_partition(labels, 6, alpha=0.5)
    scfg = ServerConfig(num_clients=6, clients_per_round=3,
                        strategy="hlora", rank_policy="random",
                        r_min=2, r_max=8)
    server = FedServer(cfg, scfg, base, [len(s) for s in shards])
    print(f"client ranks: {server.ranks.tolist()}")

    cohort_train = make_cohort_train(cfg, adamw(sim.lr))
    for rnd in range(sim.rounds):
        cohort = server.sample_cohort()
        stacked = server.cohort_adapters(cohort)         # rank-r_k truncations
        factors, masks = split_adapters(stacked)
        trainable = {"factors": factors,
                     "head": server.cohort_heads(cohort)}
        data = _stack_client_data(tokens, labels, shards, cohort, sim, rnd)
        trainable, losses = cohort_train(frozen, trainable, masks, data)
        server.update_global(join_adapters(trainable["factors"], masks),
                             cohort, stacked_heads=trainable["head"])
        print(f"round {rnd}: cohort={cohort.tolist()} "
              f"ranks={[int(server.ranks[c]) for c in cohort]} "
              f"mean_local_loss={float(jnp.mean(losses)):.4f}")

    # evaluate the aggregated global adapter
    ev_t, ev_l = make_pair_classification(sim.task, 512, seed=123,
                                          vocab_size=cfg.vocab_size)
    _, m = model_lib.loss_fn(
        server.global_params(),
        {"tokens": jnp.asarray(ev_t), "labels": jnp.asarray(ev_l)},
        cfg, remat=False)
    print(f"global model eval: acc={float(m['acc']):.3f} "
          f"loss={float(m['loss']):.3f}")


if __name__ == "__main__":
    main()
