"""Federated orchestration benchmark: the three FedSession schedulers
(sync / semi-sync / buffered-async) on one tiny convergence task, with
*measured* wire bytes per round from the serialized message format.

This is the tier-1 guard for the orchestration layer (registered as the
``fed`` section of ``benchmarks/run.py``): if a scheduler, the strategy
dispatch, or the wire accounting rots, ``--quick`` stops producing these
numbers and ``test_system::test_bench_quick_smoke_all_sections`` fails.

Reported per scheduler: final eval accuracy, events/rounds executed, and
measured downlink/uplink bytes per round — plus the rank-truncation check
(heterogeneous downlink < homogeneous r_max downlink, on serialized
bytes, not a formula).

Plus the ``mesh_*`` keys: the shard_map'd aggregation engine timed on a
1-device vs an 8-device host-CPU mesh (a subprocess, since the forced
device count must precede jax init), with bit-identity between the two
asserted in the child — the tier-1 guard that the mesh path neither rots
nor drifts numerically.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmarks.common import emit, obs_percentiles, run_mesh_child
from repro.configs import get_reduced
from repro.fed import (AsyncConfig, BufferedAsync, ClientPopulation,
                       FedSession, HierarchicalTopology, SemiSync,
                       ServerConfig, SimConfig, SyncRound)
from repro.obs import MetricsRegistry, Recorder
from repro.fed.simulation import make_experiment_setup, pretrain_backbone


def _scfg(quick: bool, **kw) -> ServerConfig:
    base = dict(num_clients=6 if quick else 20,
                clients_per_round=3 if quick else 8,
                strategy="hlora", rank_policy="random",
                r_min=2, r_max=8, seed=0)
    base.update(kw)
    return ServerConfig(**base)


def run(quick: bool = False) -> Dict:
    cfg = get_reduced("roberta-large")
    sim = SimConfig(task="mrpc",
                    num_examples=256 if quick else 2048,
                    eval_examples=64 if quick else 512,
                    rounds=2 if quick else 8,
                    local_steps=2 if quick else 6,
                    local_batch=8 if quick else 16,
                    pretrain_steps=10 if quick else 150,
                    dirichlet_alpha=0.5, lr=1e-3, seed=0)
    scfg = _scfg(quick)
    base = pretrain_backbone(cfg, sim)
    (kw, cohort_train, local_train, data_fn, client_data_fn,
     eval_fn) = make_experiment_setup(cfg, sim, scfg, base)
    n = scfg.num_clients
    speeds = np.linspace(0.5, 2.0, n)          # 4x speed spread
    out: Dict = {}

    def _record(name, history, t0):
        rounds = len(history.get("round", history.get("time", [])))
        out[f"{name}_final_acc"] = history["eval_acc"][-1]
        if "downlink_bytes" in history:
            out[f"{name}_downlink_bytes_per_round"] = float(
                np.mean(history["downlink_bytes"]))
            out[f"{name}_uplink_bytes_per_round"] = float(
                np.mean(history["uplink_bytes"]))
        emit(f"fed/{name}", (time.time() - t0) * 1e6 / max(rounds, 1),
             f"final_acc={history['eval_acc'][-1]:.4f} "
             + (f"bytes/round=down:"
                f"{out.get(f'{name}_downlink_bytes_per_round', 0):.0f}"
                f"/up:{out.get(f'{name}_uplink_bytes_per_round', 0):.0f}"
                if "downlink_bytes" in history else
                f"events={rounds}"))

    # -- sync (cohort barrier — the paper's mode) ---------------------------
    t0 = time.time()
    rec = Recorder()
    metrics = MetricsRegistry()
    sess = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"],
                      recorder=rec, metrics=metrics)
    h = SyncRound().run(sess, cohort_train, data_fn, sim.rounds,
                        eval_fn=eval_fn)
    _record("sync", h, t0)
    # recorder-derived round timing + registry-measured wire bytes: the
    # SAME clock/counters the session records with, not bench timers
    rs = obs_percentiles(metrics, "fed.round_s", scale=1e3)
    out["obs_round_ms_p50"] = rs.get("p50", 0.0)
    out["obs_round_ms_p99"] = rs.get("p99", 0.0)
    nr = max(sess.rounds_done, 1)
    out["obs_downlink_bytes_per_round"] = \
        metrics.counter("fed.downlink_bytes").value / nr
    out["obs_uplink_bytes_per_round"] = \
        metrics.counter("fed.uplink_bytes").value / nr
    out["obs_events"] = len(rec)
    emit("fed/obs_rounds", rs.get("p50", 0.0) * 1e3,
         f"round p50={out['obs_round_ms_p50']:.0f}ms "
         f"p99={out['obs_round_ms_p99']:.0f}ms, bytes/round=down:"
         f"{out['obs_downlink_bytes_per_round']:.0f}/up:"
         f"{out['obs_uplink_bytes_per_round']:.0f} "
         f"({out['obs_events']} trace events)")

    # -- semi-sync (deadline straggler cutoff) ------------------------------
    t0 = time.time()
    sess = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"])
    h = SemiSync(speeds=speeds, deadline_quantile=0.7).run(
        sess, cohort_train, data_fn, sim.rounds, eval_fn=eval_fn)
    out["semisync_stragglers_total"] = int(sum(h["stragglers"]))
    _record("semisync", h, t0)

    # -- buffered async (K-buffer, one engine call per flush) ----------------
    t0 = time.time()
    sess = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"])
    num_events = sim.rounds * scfg.clients_per_round
    h = BufferedAsync(speeds=speeds, buffer_size=scfg.clients_per_round,
                      acfg=AsyncConfig(base_weight=0.5)).run(
        sess, local_train, client_data_fn, num_events,
        eval_fn=eval_fn, eval_every=scfg.clients_per_round)
    out["async_final_acc"] = h["eval_acc"][-1]
    out["async_flushes"] = len(h["flush_events"])
    out["async_mean_staleness"] = float(np.mean(h["staleness"]))
    down, up = sess.comm_totals()["downlink"], sess.comm_totals()["uplink"]
    out["async_downlink_bytes_per_event"] = down / max(num_events, 1)
    out["async_uplink_bytes_per_event"] = up / max(num_events, 1)
    emit("fed/buffered_async", (time.time() - t0) * 1e6 / num_events,
         f"final_acc={h['eval_acc'][-1]:.4f} "
         f"flushes={out['async_flushes']} (K={scfg.clients_per_round}) "
         f"mean_staleness={out['async_mean_staleness']:.2f}")

    # -- wire accounting: heterogeneous ranks measurably cheaper ------------
    down_by_policy = {}
    for policy in ("uniform", "random"):
        sess = FedSession(cfg, _scfg(quick, rank_policy=policy), base,
                          client_sizes=kw["client_sizes"])
        cohort = np.arange(scfg.clients_per_round)
        sess.broadcast_cohort(cohort)
        down_by_policy[policy] = sess.comm_log["downlink"][-1] \
            / len(cohort)
    out["downlink_bytes_uniform_r8"] = down_by_policy["uniform"]
    out["downlink_bytes_random_2_8"] = down_by_policy["random"]
    ratio = down_by_policy["random"] / down_by_policy["uniform"]
    out["downlink_hetero_over_homo"] = ratio
    assert ratio < 1.0, "rank-truncated payloads must beat r_max payloads"
    emit("fed/wire_rank_truncation", 0.0,
         f"measured broadcast bytes/client: random[2,8]="
         f"{down_by_policy['random']:.0f} vs uniform r8="
         f"{down_by_policy['uniform']:.0f} ({100 * ratio:.0f}%)")

    # -- hierarchical two-tier aggregation (stack: lossless; engine:
    #    pre-merged edge updates that shrink root fan-in bytes) ------------
    t0 = time.time()
    topo = HierarchicalTopology(num_edges=2, edge_mode="stack")
    finals = {}
    for name, topology in (("flat", None), ("hier", topo)):
        sess = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"])
        SyncRound(topology=topology).run(sess, cohort_train, data_fn,
                                         sim.rounds, eval_fn=eval_fn)
        finals[name] = sess
    bit_identical = all(
        bool(np.array_equal(
            np.asarray(finals["hier"].global_lora[t][leaf]),
            np.asarray(finals["flat"].global_lora[t][leaf])))
        for t in finals["flat"].global_lora for leaf in ("A", "B", "mask"))
    assert bit_identical, "stack-mode hierarchy drifted from flat"
    out["hier_bit_identical"] = int(bit_identical)
    edge_rows = [v for k_, v in finals["hier"].comm_log.items()
                 if k_.startswith("edge")]
    out["hier_edge_uplink_bytes_per_round"] = float(
        sum(sum(r) for r in edge_rows) / sim.rounds)
    sess = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"])
    SyncRound(topology=HierarchicalTopology(
        num_edges=2, edge_mode="engine")).run(
        sess, cohort_train, data_fn, sim.rounds, eval_fn=eval_fn)
    out["hier_engine_edge_bytes_per_round"] = float(
        sum(sum(v) for k_, v in sess.comm_log.items()
            if k_.startswith("edge")) / sim.rounds)
    emit("fed/hierarchical", (time.time() - t0) * 1e6 / sim.rounds,
         f"stack bit_identical={bit_identical} "
         f"edge->root bytes/round: stack="
         f"{out['hier_edge_uplink_bytes_per_round']:.0f} vs engine="
         f"{out['hier_engine_edge_bytes_per_round']:.0f} (2 edges)")

    # -- population-scale round: lazy materialization over 2k/10k clients --
    t0 = time.time()
    pop = ClientPopulation.synthetic(2000 if quick else 10_000, seed=0,
                                     vocab_size=cfg.vocab_size)
    scfg_pop = _scfg(quick, num_clients=pop.size)
    sess = FedSession(cfg, scfg_pop, base, population=pop,
                      sampler="rank_stratified")
    h = SyncRound().run(sess, cohort_train,
                        pop.data_fn(sim.local_steps, sim.local_batch),
                        sim.rounds, eval_fn=eval_fn)
    assert pop.max_resident <= scfg_pop.clients_per_round, \
        "population round materialized more than the cohort"
    out["pop_clients"] = float(pop.size)
    out["pop_cohort"] = float(scfg_pop.clients_per_round)
    out["pop_max_resident"] = float(pop.max_resident)
    out["pop_downlink_bytes_per_round"] = float(
        np.mean(h["downlink_bytes"]))
    out["pop_uplink_bytes_per_round"] = float(np.mean(h["uplink_bytes"]))
    emit("fed/population", (time.time() - t0) * 1e6 / sim.rounds,
         f"{pop.size} clients, cohort={scfg_pop.clients_per_round}, "
         f"max_resident={pop.max_resident} (rank-stratified sampler), "
         f"final_acc={h['eval_acc'][-1]:.4f}")

    # -- mesh scaling: shard_map'd aggregation, 1 vs 8 host devices ---------
    out.update(run_mesh_child("benchmarks.bench_fed", quick))
    emit("fed/mesh_scaling", out["mesh_agg_us_sharded"],
         f"agg {out['mesh_agg_us_single']:.0f}us@1dev -> "
         f"{out['mesh_agg_us_sharded']:.0f}us@{out['mesh_devices']}dev "
         f"({out['mesh_agg_speedup']:.2f}x), "
         f"bit_identical={out['mesh_agg_bit_identical']}")
    return out


def _mesh_child(quick: bool) -> None:
    """Child-process half of the mesh-scaling section (8 forced host
    devices): time the aggregation engine's jitted round on one device
    and shard_map'd over the mesh, and assert the factors/spectra are
    bit-identical. Prints one MESH_RESULT json line for the parent."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import MESH_RESULT_TAG, time_fn
    from repro.core.agg_engine import AggregationEngine
    from repro.launch.mesh import make_host_mesh

    k, layers, d, r = (4, 4, 32, 4) if quick else (16, 12, 128, 8)
    key = jax.random.PRNGKey(0)
    adapters = {}
    for j, t in enumerate(("q", "v")):
        ks = jax.random.split(jax.random.fold_in(key, j), 3)
        adapters[t] = {
            "A": jax.random.normal(ks[0], (k, layers, d, r), jnp.float32),
            "B": jax.random.normal(ks[1], (k, layers, r, d), jnp.float32),
            "mask": (jax.random.uniform(ks[2], (k, layers, r)) > 0.3
                     ).astype(jnp.float32)}
    eta = jnp.ones((k,)) / k
    mesh = make_host_mesh(data=8)
    e1 = AggregationEngine(factored_impl="qr")
    e8 = AggregationEngine(factored_impl="qr", mesh=mesh)
    o1, s1 = e1(adapters, eta, 8.0)
    o8, s8 = e8(adapters, eta, 8.0)
    identical = all(
        bool(jnp.array_equal(o1[t][leaf], o8[t][leaf]))
        for t in o1 for leaf in ("A", "B", "mask")) and all(
        bool(jnp.array_equal(s1[t], s8[t])) for t in s1)
    assert identical, "sharded aggregation drifted from single-device"
    iters = 3 if quick else 10
    us1 = time_fn(lambda: e1(adapters, eta, 8.0), warmup=1, iters=iters)
    us8 = time_fn(lambda: e8(adapters, eta, 8.0), warmup=1, iters=iters)
    import json as json_mod
    print(MESH_RESULT_TAG + json_mod.dumps({
        "mesh_devices": 8,
        "mesh_agg_batch_items": 2 * layers,
        "mesh_agg_us_single": us1,
        "mesh_agg_us_sharded": us8,
        "mesh_agg_speedup": us1 / us8,
        "mesh_agg_bit_identical": int(identical)}), flush=True)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh-child", action="store_true")
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    if a.mesh_child:
        _mesh_child(a.quick)
    else:
        run(quick=True)
