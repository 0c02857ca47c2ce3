"""Eq. 1 bias measurement — the paper's §Limitations claim, quantified.

How far is naive separate averaging (ΣηB)(ΣηA) from the exact FedAvg
Ση(BA), as a function of (a) client divergence (local steps) and
(b) rank heterogeneity? Adapters come from REAL local training on
non-IID shards, not synthetic noise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs import get_reduced
from repro.core.aggregate import aggregation_bias
from repro.data import dirichlet_partition, make_pair_classification
from repro.fed.client import make_cohort_train, split_adapters, split_head
from repro.fed.server import FedServer, ServerConfig
from repro.fed.simulation import SimConfig, _stack_client_data, \
    pretrain_backbone
from repro.optim import adamw


def run(local_steps_grid=(2, 8, 24), quick=False):
    # quick is a smoke mode: one grid point at toy data/pretrain sizes —
    # it checks the pipeline runs, not the bias magnitudes
    if quick:
        local_steps_grid = (2,)
    cfg = get_reduced("roberta-large")
    sim = SimConfig(task="rte",
                    num_examples=256 if quick else 2048,
                    pretrain_steps=10 if quick else 200,
                    dirichlet_alpha=0.1, lr=1e-3, local_batch=16)
    base = pretrain_backbone(cfg, sim)
    frozen, _ = split_head(base)
    tokens, labels = make_pair_classification(
        sim.task, sim.num_examples, seed=0, vocab_size=cfg.vocab_size)
    shards = dirichlet_partition(labels, 10, sim.dirichlet_alpha, seed=0)
    out = {}
    for steps in local_steps_grid:
        scfg = ServerConfig(num_clients=10, clients_per_round=6,
                            strategy="hlora", rank_policy="uniform", seed=0)
        server = FedServer(cfg, scfg, base, [len(s) for s in shards])
        cohort = server.sample_cohort()
        stacked = server.cohort_adapters(cohort)
        factors, masks = split_adapters(stacked)
        trainable = {"factors": factors, "head": server.cohort_heads(cohort)}
        sim_i = SimConfig(**{**sim.__dict__, "local_steps": steps})
        data = _stack_client_data(tokens, labels, shards, cohort, sim_i, 0)
        cohort_train = make_cohort_train(cfg, adamw(sim.lr))
        trainable, _ = cohort_train(frozen, trainable, masks, data)
        eta = server.cohort_weights(cohort)
        biases = []
        for t, f in trainable["factors"].items():
            st_ = {"A": f["A"], "B": f["B"], "mask": masks[t]}
            biases.append(float(aggregation_bias(st_, eta, cfg.lora.alpha)))
        out[steps] = float(np.mean(biases))
        emit(f"bias/local_steps={steps}",
             f"relative_bias={out[steps]:.4f}")
    return out


if __name__ == "__main__":
    run()
