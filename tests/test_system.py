"""End-to-end behaviour of the full system (the paper's pipeline),
plus the benchmark harness's result-merge contract."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import lora
from repro.fed import ServerConfig, SimConfig, run_centralized, run_experiment
from repro.fed.simulation import pretrain_backbone
from repro.models import model as model_lib


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("roberta-large")
    sim = SimConfig(task="qqp", num_examples=1536, eval_examples=384,
                    rounds=4, local_steps=6, local_batch=16,
                    pretrain_steps=120, lr=1e-3, seed=0)
    base = pretrain_backbone(cfg, sim)
    return cfg, sim, base


def test_pipeline_all_strategies_finite(setup):
    cfg, sim, base = setup
    finals = {}
    for strat, policy in [("naive", "uniform"), ("hlora", "uniform"),
                          ("hlora", "random")]:
        scfg = ServerConfig(num_clients=8, clients_per_round=4,
                            strategy=strat, rank_policy=policy, seed=0)
        h = run_experiment(cfg, sim, scfg, base_params=base)
        assert np.isfinite(h["train_loss"]).all()
        assert np.isfinite(h["eval_acc"]).all()
        finals[f"{strat}/{policy}"] = h["eval_acc"][-1]
    # every strategy must at least beat chance after training on the easy task
    for k, v in finals.items():
        assert v > 0.5, (k, v)


def test_centralized_upper_bound_runs(setup):
    cfg, sim, base = setup
    h = run_centralized(cfg, sim, rank=8, base_params=base)
    assert h["eval_acc"][-1] > 0.5
    assert np.isfinite(h["train_loss"]).all()


def test_heterogeneous_comm_volume_less_than_homogeneous(setup):
    """Claim C4: HLoRA comm ∝ r_k — heterogeneous cohorts transmit less."""
    cfg, sim, base = setup
    from repro.fed.server import FedServer
    scfg_h = ServerConfig(num_clients=8, clients_per_round=8,
                          strategy="hlora", rank_policy="random",
                          r_min=2, r_max=8, seed=0)
    scfg_u = ServerConfig(num_clients=8, clients_per_round=8,
                          strategy="hlora", rank_policy="uniform",
                          r_max=8, seed=0)
    sizes = [64] * 8
    sv_h = FedServer(cfg, scfg_h, base, sizes)
    sv_u = FedServer(cfg, scfg_u, base, sizes)

    def total_bytes(server):
        tot = 0
        for cid in range(8):
            r = int(server.ranks[cid])
            for t, ad in server.global_lora.items():
                tot += lora.comm_bytes(ad, r)
        return tot

    assert total_bytes(sv_h) < total_bytes(sv_u)


def test_fed_lora_deployable_merge(setup):
    """Merged weights (deployment path) match adapter forward."""
    cfg, sim, base = setup
    params = model_lib.init_params(jax.random.PRNGKey(1), cfg)
    for t, ad in params["lora"].items():
        params["lora"][t]["B"] = jax.random.normal(
            jax.random.PRNGKey(hash(t) % 97), ad["B"].shape) * 0.02
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.zeros((2,), jnp.int32)}
    logits_adapter, _ = model_lib.forward(params, batch, cfg, remat=False,
                                          q_chunk=16)
    merged = jax.tree.map(lambda x: x, params)
    name_map = {"q": "wq", "v": "wv"}
    for t, ad in params["lora"].items():
        merged["layers"]["attn"][name_map[t]] = lora.merge(
            merged["layers"]["attn"][name_map[t]], ad, cfg.lora.alpha)
        merged["lora"][t] = dict(ad, B=jnp.zeros_like(ad["B"]))
    logits_merged, _ = model_lib.forward(merged, batch, cfg, remat=False,
                                         q_chunk=16)
    np.testing.assert_allclose(np.asarray(logits_adapter),
                               np.asarray(logits_merged),
                               rtol=2e-3, atol=2e-3)


def test_invariant_lint_full_tree_clean():
    """The invariant lint suite (repro.analysis) over the REAL tree:
    clock/RNG/hash/retrace/atomic-write discipline are wire contracts
    once edges run as separate processes — a violation anywhere in
    src/repro is a tier-1 failure at authoring time, not a flaky
    divergence at 10k clients. Sanctioned sites are pragma'd or
    allowlisted (see src/repro/analysis/README.md); everything else
    must be clean."""
    from repro.analysis import all_rules, run_paths
    root = os.path.join(os.path.dirname(__file__), os.pardir,
                        "src", "repro")
    assert len(all_rules()) >= 5
    findings = run_paths([root])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_bench_quick_smoke_all_sections(tmp_path):
    """Tier-1 guard against benchmark rot: ``benchmarks.run --quick``
    must execute EVERY section end-to-end on tiny shapes and land a
    number for each in the results json. This is what catches an API
    drift in a benchmark script before it silently stops producing the
    paper's tables."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.run import ALL, main
    out = str(tmp_path / "bench.json")
    rc = main(["--quick", "--out", out,
               "--dryrun-jsonl", str(tmp_path / "missing.jsonl")])
    got = json.load(open(out))
    assert rc == 0, got.get("_errors")
    assert set(ALL) <= set(got), sorted(set(ALL) - set(got))
    # the speculative serving section reports the new metrics; the
    # exactness/acceptance asserts are deterministic — the speedup is
    # wall-clock on a noisy box, so only its presence is tier-1
    assert got["serve"]["spec_forced_exact"] == 1.0
    assert got["serve"]["spec_forced_acceptance"] == 1.0
    assert got["serve"]["spec_forced_speedup_vs_plain"] > 0
    # the mesh-scaling subsections run in forced-host-device children;
    # equivalence (bit-identity / byte-exactness vs single-device) is
    # deterministic and pinned — the speedups are wall-clock, presence
    # only
    assert got["fed"]["mesh_agg_bit_identical"] == 1
    assert got["fed"]["mesh_agg_speedup"] > 0
    assert got["serve"]["mesh_scaling_exact"] == 1.0
    assert got["serve"]["mesh_traces_flat"] == 1
    assert got["serve"]["mesh_tok_per_s_sharded"] > 0
    # the observability section: trace export validated, JSONL round-
    # tripped, and the promised span names present (all deterministic);
    # recorder-derived latency percentiles are wall-clock, presence only
    assert got["obs"]["obs_jsonl_roundtrip"] == 1
    assert got["obs"]["obs_span_names_ok"] == 1
    assert got["obs"]["obs_events"] > 0 and got["obs"]["obs_tracks"] > 0
    assert got["serve"]["obs_ttft_p99_ms"] > 0
    assert got["serve"]["obs_req_tok_s_p50"] > 0
    assert got["fed"]["obs_round_ms_p50"] > 0
    assert got["fed"]["obs_downlink_bytes_per_round"] > 0
    # the watching layer (PR 8): SLOs evaluate clean over the smoke
    # run, the HTML ops report renders non-empty, and the mesh child's
    # events were collected, clock-rebased, and merged into a trace
    # that validates
    assert got["obs"]["obs_slo_ok"] == 1
    assert got["obs"]["obs_series"] > 0
    assert got["obs"]["obs_report_bytes"] > 0
    assert got["obs"]["obs_child_events"] > 0
    assert got["obs"]["obs_merged_valid"] == 1
    assert got["obs"]["obs_merged_events"] > got["obs"]["obs_child_events"]
    # per-class TTFT SLO attainment (generous targets: deterministic)
    assert got["serve"]["obs_slo_interactive_attainment"] == 1.0
    assert got["serve"]["obs_slo_batch_attainment"] == 1.0
    assert got["serve"]["obs_slo_interactive_total"] > 0
    # hierarchical two-tier aggregation: stack mode is pinned bit-identical
    # to flat, and the edge->root tier carries measured wire bytes
    assert got["fed"]["hier_bit_identical"] == 1
    assert got["fed"]["hier_edge_uplink_bytes_per_round"] > 0
    assert got["fed"]["hier_engine_edge_bytes_per_round"] > 0
    # population-scale round: lazy materialization never exceeds cohort
    assert got["fed"]["pop_clients"] >= 2000
    assert got["fed"]["pop_max_resident"] <= got["fed"]["pop_cohort"]
    assert got["fed"]["pop_uplink_bytes_per_round"] > 0
    # wire codec curve: none is exact, quantized/truncated curves are
    # strictly cheaper than raw f32 (deterministic byte counts)
    assert got["comm"]["codec_none_rel_err"] == 0.0
    assert got["comm"]["codec_int8_bytes"] < got["comm"]["codec_bf16_bytes"]
    assert got["comm"]["codec_bf16_bytes"] < got["comm"]["codec_none_bytes"]
    assert got["comm"]["codec_topk2_bytes"] < got["comm"]["codec_none_bytes"]
    # the invariant lint suite ran through its real CLI entry point:
    # the pass registry lists all >=5 rules and the shipped tree is
    # clean (both deterministic — a broken registry import or a new
    # un-pragma'd violation fails the smoke run here)
    assert got["analysis"]["rules_listed"] >= 5
    assert got["analysis"]["cli_list_rc"] == 0
    assert got["analysis"]["tree_clean"] == 1
    # every invocation appends to the perf history beside --out
    hist = str(tmp_path / "bench_history.jsonl")
    assert os.path.exists(hist)
    entries = [json.loads(l) for l in open(hist) if l.strip()]
    assert len(entries) == 1 and entries[0]["quick"] is True
    assert "serve.engine_tok_per_s" in entries[0]["results"]


def test_bench_merge_preserves_sections_on_failure(tmp_path):
    """A failing bench section must not clobber its previous good numbers
    (they stay, the error lands under '_errors'), a succeeding section
    clears its stale error, and untouched sections persist."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.run import merge_results
    path = str(tmp_path / "bench.json")
    merge_results(path, {"serve": {"x": 1}, "svd": {"y": 2}}, {})
    merge_results(path, {"svd": {"y": 3}}, {"serve": "RuntimeError: boom"})
    got = json.load(open(path))
    assert got["serve"] == {"x": 1}          # old numbers survive
    assert got["svd"] == {"y": 3}            # re-run section updated
    assert got["_errors"] == {"serve": "RuntimeError: boom"}
    merge_results(path, {"serve": {"x": 9}}, {})
    got = json.load(open(path))
    assert got["serve"] == {"x": 9} and "_errors" not in got
    # corrupt previous file: start fresh instead of crashing
    with open(path, "w") as f:
        f.write("{not json")
    merge_results(path, {"comm": {"z": 1}}, {})
    assert json.load(open(path)) == {"comm": {"z": 1}}


def test_bench_regression_gate(tmp_path):
    """The perf-regression gate at unit level: identical back-to-back
    runs pass, a >20% move in the bad direction on a curated key fails,
    a within-threshold move passes, and keys missing from either run
    are skipped (new benches don't break the gate)."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.run import (QUICK_REGRESSION_THRESHOLD,
                                REGRESSION_KEYS, REGRESSION_THRESHOLD,
                                append_history, check_regressions,
                                flatten_numeric, history_path_for)
    base = {"serve.engine_tok_per_s": 1000.0,
            "serve.obs_ttft_p99_ms": 10.0,
            "fed.obs_round_ms_p99": 200.0}
    # identical back-to-back: clean
    assert check_regressions(base, dict(base)) == []
    # within threshold (15% either way): clean
    ok = {"serve.engine_tok_per_s": 850.0,     # -15%, higher-is-better
          "serve.obs_ttft_p99_ms": 11.5,       # +15%, lower-is-better
          "fed.obs_round_ms_p99": 200.0}
    assert check_regressions(base, ok) == []
    # injected regressions: throughput -30%, latency +50%
    bad = {"serve.engine_tok_per_s": 700.0,
           "serve.obs_ttft_p99_ms": 15.0,
           "fed.obs_round_ms_p99": 200.0}
    hits = check_regressions(base, bad)
    assert {h[0] for h in hits} == {"serve.engine_tok_per_s",
                                    "serve.obs_ttft_p99_ms"}
    # IMPROVEMENTS never trip the gate (direction-aware)
    better = {"serve.engine_tok_per_s": 5000.0,
              "serve.obs_ttft_p99_ms": 1.0,
              "fed.obs_round_ms_p99": 50.0}
    assert check_regressions(base, better) == []
    # missing keys (either side) and zero/negative baselines: skipped
    assert check_regressions({}, bad) == []
    assert check_regressions({"serve.engine_tok_per_s": 0.0},
                             {"serve.engine_tok_per_s": 1.0}) == []
    # mesh keys are deliberately NOT gated (host-device artifacts)
    assert not any(k.startswith(("serve.mesh_", "fed.mesh_"))
                   for k in REGRESSION_KEYS)
    assert REGRESSION_THRESHOLD == pytest.approx(0.20)
    # quick smoke shapes jitter ~±30% wall-clock, so quick mode gates
    # wider — still far under the 2-10x moves a real perf rot produces
    assert QUICK_REGRESSION_THRESHOLD > REGRESSION_THRESHOLD
    bad30 = {"serve.engine_tok_per_s": 700.0}   # -30%: noise at --quick
    assert check_regressions(base, bad30,
                             threshold=QUICK_REGRESSION_THRESHOLD) == []
    bad60 = {"serve.engine_tok_per_s": 400.0}   # -60%: rot in any mode
    assert len(check_regressions(base, bad60,
                                 threshold=QUICK_REGRESSION_THRESHOLD)) == 1

    # flatten drops private keys, non-numerics, bools, non-dict
    # sections (roofline rows), and non-str keys (convergence sub-dicts
    # keyed by int rank)
    flat = flatten_numeric({"serve": {"a": 1, "_p": 2, "s": "x",
                                      "b": True},
                            "convergence": {4: {"acc": 0.9}, "n": 2},
                            "roofline": [{"gflops": 1.0}],
                            "_errors": {"x": "y"}})
    assert flat == {"serve.a": 1.0, "convergence.n": 2.0}

    # history: same-mode previous entry is returned, modes are disjoint
    hp = str(tmp_path / "h.jsonl")
    assert append_history(hp, {"k": 1.0}, quick=True) is None
    assert append_history(hp, {"k": 2.0}, quick=False) is None
    prev = append_history(hp, {"k": 3.0}, quick=True)
    assert prev["results"] == {"k": 1.0}
    assert len([l for l in open(hp) if l.strip()]) == 3
    # torn trailing line (crashed writer) is dropped, not fatal
    with open(hp, "a") as f:
        f.write("{torn")
    prev = append_history(hp, {"k": 4.0}, quick=True)
    assert prev["results"] == {"k": 3.0}

    assert history_path_for("results/bench_results.json") == \
        os.path.join("results", "bench_history.jsonl")
    assert history_path_for(str(tmp_path / "bench_quick.json")) == \
        str(tmp_path / "bench_quick_history.jsonl")


def test_bench_check_flag_fails_on_injected_regression(tmp_path):
    """--check end-to-end through main() without running real benches:
    seed the history with a strong previous entry, run only the cheap
    ``comm`` section, and verify rc. Since comm has no curated keys,
    the gate passes vacuously; then inject a history where the current
    run WOULD regress by pre-seeding overlapping keys via a fake
    section result written through append_history + check directly."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.run import check_regressions
    # the rc=2 path is main()'s only logic on top of check_regressions;
    # exercise the decision table here (running two full --quick passes
    # back-to-back in tier-1 would double suite time for no new signal)
    prev = {"serve.engine_tok_per_s": 1000.0}
    assert check_regressions(prev, {"serve.engine_tok_per_s": 799.0})
    assert not check_regressions(prev, {"serve.engine_tok_per_s": 801.0})
