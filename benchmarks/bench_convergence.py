"""Fig. 3 + Table 1 reproduction: federated strategies across the three
task stand-ins, multi-seed. One set of runs feeds both outputs:

  Fig. 3a/c/e — naive vs HLoRA (homogeneous rank): convergence curves
  Fig. 3b/d/f — HLoRA homogeneous vs heterogeneous rank
  Table 1     — final accuracy per strategy per task (+ the beyond-paper
                FLoRA stacking baseline, a one-class strategy addition)

Paper claims validated: C1 (hlora ≥ naive in convergence/final acc),
C2 (hetero ranks competitive/better despite smaller average rank),
C3 (centralized is the upper bound).

Each run is a thin driver over the unified FedSession API
(``run_experiment`` = FedSession + SyncRound); strategy rows are
resolved to AggregationStrategy objects by name.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.common import emit
from repro.configs import get_reduced
from repro.fed import (ServerConfig, SimConfig, rounds_to_target,
                       run_centralized, run_experiment)
from repro.fed.simulation import pretrain_backbone

STRATEGIES = [
    ("centralized", None, "Centralised LoRA Fine-Tuning"),
    ("hlora", "random", "Heterogeneous Rank Reconstruction"),
    ("hlora", "uniform", "Reconstruction Re-Decomposition (Homogeneous)"),
    ("naive", "uniform", "Direct Application of LoRA (Naive)"),
    ("naive", "random", "Zero-Padding Heterogeneous (Cho et al.)"),
    ("flora", "random", "FLoRA Stacking Heterogeneous (Wang et al.)"),
]


def run(tasks=("mrpc", "rte", "qqp"), seeds=(0, 1), rounds=14,
        quick=False) -> Dict:
    # quick is a smoke mode: one task/seed, toy data, a few rounds — it
    # checks every strategy still trains end-to-end, not the accuracies
    if quick:
        tasks, seeds, rounds = ("mrpc",), (0,), 3
    cfg = get_reduced("roberta-large")
    results: Dict[str, Dict[str, List]] = {}
    for task in tasks:
        sim0 = SimConfig(task=task,
                         num_examples=512 if quick else 4096,
                         eval_examples=128 if quick else 1024,
                         rounds=rounds, local_steps=4 if quick else 8,
                         local_batch=16,
                         pretrain_steps=20 if quick else 300,
                         dirichlet_alpha=0.3, lr=1e-3)
        base = pretrain_backbone(cfg, sim0)
        for strat, policy, label in STRATEGIES:
            curves = []
            for seed in seeds:
                sim = SimConfig(**{**sim0.__dict__, "seed": seed})
                if strat == "centralized":
                    h = run_centralized(cfg, sim, rank=8, base_params=base)
                else:
                    scfg = ServerConfig(
                        num_clients=10 if quick else 30,
                        clients_per_round=4 if quick else 10,
                        strategy=strat, rank_policy=policy,
                        r_min=2, r_max=8, seed=seed)
                    # curves only — bench_comm owns the wire-byte numbers
                    h = run_experiment(cfg, sim, scfg, base_params=base,
                                       track_comm=False)
                curves.append(h["eval_acc"])
            mean_curve = np.mean(np.array(curves), axis=0)
            key = f"{task}/{label}"
            results[key] = {
                "curve": mean_curve.tolist(),
                "final": float(np.mean([c[-1] for c in curves])),
                "best": float(np.mean([max(c) for c in curves])),
                "mean_last3": float(mean_curve[-3:].mean()),
            }
            tgt = 0.66
            r2t = rounds_to_target({"round": list(range(len(mean_curve))),
                                    "eval_acc": mean_curve.tolist()}, tgt)
            results[key]["rounds_to_66"] = r2t if r2t is not None else -1
            emit(f"fig3/{task}/{label.replace(' ', '_')}",
                 f"final={results[key]['final']:.4f} "
                 f"best={results[key]['best']:.4f} "
                 f"rounds_to_{tgt}={r2t}")
    return results


def table1(results: Dict) -> str:
    tasks = sorted({k.split("/")[0] for k in results})
    labels = [l for _, _, l in STRATEGIES]
    lines = ["| Training strategy | " + " | ".join(t.upper() for t in tasks)
             + " |",
             "|---|" + "---|" * len(tasks)]
    for label in labels:
        row = [label]
        for t in tasks:
            r = results.get(f"{t}/{label}")
            row.append(f"{100 * r['best']:.1f}" if r else "–")
        lines.append("| " + " | ".join(row) + " |")
    return "\n".join(lines)


if __name__ == "__main__":
    res = run()
    print(table1(res))
