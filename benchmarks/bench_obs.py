"""Observability smoke bench: capture a small serve + fed trace on one
shared recorder, export it, watch it, and assert the exports hold up.

Registered as the ``obs`` section of ``benchmarks/run.py`` (tier-1 runs
it via ``--quick``), this is the guard that the observability layer
itself cannot rot: a tiny serving wave and a tiny federated round record
into ONE recorder, then

* the Chrome trace-event export validates (required keys, monotone
  non-overlapping spans per track) and lands in ``results/`` where it
  can be dropped straight into perfetto,
* the JSONL export round-trips losslessly back to the in-memory events,
* the span names the instrumentation promises (prefill/decode on the
  serve side, broadcast/collect/aggregate rounds on the fed side) are
  actually present,
* the *watching* layer works end to end: the events fold into a
  ``SeriesStore``, an ``SLOMonitor`` evaluates clean over them, and the
  static HTML ops report + terminal snapshot render from the result,
* cross-process collection works against a real child: a mesh child
  (2 forced host devices) records its own wave, ``dump_stream``\\ s it
  with a clock handshake, and the parent ``merge_streams`` the child
  events onto its own timeline into a single validated Chrome trace.
"""
from __future__ import annotations

import os
from typing import Dict

import jax
import numpy as np

from benchmarks.common import emit, export_trace, run_mesh_child
from repro.configs import get_reduced
from repro.fed import FedSession, ServerConfig
from repro.models import model as model_lib
from repro.obs import (MetricsRegistry, Objective, Recorder, SLOMonitor,
                       SeriesStore, clock_handshake, merge_streams,
                       read_jsonl, read_stream, snapshot_text,
                       validate_chrome_trace, write_chrome_trace,
                       write_html)
from repro.serve import AdapterRegistry, ServeEngine
from repro.serve.oracle import make_demo_adapter

# generous ceilings: these SLOs guard "the pipeline works", not perf —
# a tiny reduced model on host CPU clears them by orders of magnitude,
# so obs_slo_ok == 1 is deterministic while still exercising the full
# objective -> fold -> evaluate -> report path
_SLO_OBJECTIVES = (
    Objective("serve_ttft", series="first_token.ttft_s",
              threshold=60.0, target=0.9),
    Objective("fed_aggregate", series="span.fed.aggregate",
              threshold=60.0, target=0.9),
)


def _tiny_serve_engine(rec: Recorder, metrics: MetricsRegistry, mesh=None,
                       slo_ttft_s=None):
    cfg = get_reduced("gemma-2b")
    key = jax.random.PRNGKey(0)
    params = model_lib.init_params(key, cfg)
    registry = AdapterRegistry(cfg, capacity=2)
    for i in range(2):
        registry.register(f"client{i}", make_demo_adapter(
            jax.random.fold_in(key, 100 + i), cfg, 2 + 2 * i))
    engine = ServeEngine(params, cfg, registry, max_batch=2, max_seq=16,
                         page_size=4, prefill_chunk=8, mesh=mesh,
                         recorder=rec, metrics=metrics,
                         slo_ttft_s=slo_ttft_s)
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 3), (2, 8), 3, cfg.vocab_size))
    return engine, prompts


def _serve_half(rec: Recorder, metrics: MetricsRegistry, results: Dict):
    engine, prompts = _tiny_serve_engine(rec, metrics)
    for i in range(2):
        engine.submit(prompts[i], f"client{i}", max_new_tokens=4)
    engine.run()
    results["obs_serve_steps"] = engine.steps


def _fed_half(rec: Recorder, metrics: MetricsRegistry, results: Dict):
    """Server-side round only (no client training — the spans under test
    are the session's): broadcast -> collect -> aggregate, with measured
    wire bytes landing on the shared timeline."""
    cfg = get_reduced("roberta-large")
    scfg = ServerConfig(num_clients=4, clients_per_round=2,
                        strategy="hlora", rank_policy="random",
                        r_min=2, r_max=8, seed=0)
    base = model_lib.init_params(jax.random.PRNGKey(1), cfg)
    sess = FedSession(cfg, scfg, base, recorder=rec, metrics=metrics)
    cohort = sess.sample_cohort()
    stacked, heads = sess.broadcast_cohort(cohort)
    # the broadcast stack doubles as the "trained" cohort — the wire and
    # aggregation paths are what this section exercises
    tree, up_heads = sess.collect_updates(cohort, stacked,
                                          heads if heads else None)
    sess.aggregate_round(tree, cohort, stacked_heads=up_heads)
    results["obs_fed_rounds"] = sess.rounds_done
    results["obs_fed_downlink_bytes"] = \
        metrics.counter("fed.downlink_bytes").value


def _watch(rec: Recorder, metrics: MetricsRegistry, results: Dict):
    """Fold the recorded run into series, evaluate SLOs over them, and
    render the ops report (HTML + terminal snapshot)."""
    store = SeriesStore(bucket_s=0.25)
    store.fold(rec.events())
    results["obs_series"] = len(store.names())
    assert store.has("first_token.ttft_s"), "TTFT series missing"
    assert store.has("span.fed.aggregate"), "aggregate span series missing"

    slo = SLOMonitor(list(_SLO_OBJECTIVES), recorder=rec)
    slo.fold(rec.events())
    states = slo.evaluate()
    results["obs_slo_ok"] = int(
        not any(st.in_violation for st in states.values()))
    assert results["obs_slo_ok"] == 1, \
        f"smoke SLOs violated: {[n for n, s in states.items() if s.in_violation]}"

    report = write_html("results/obs_report.html",
                        title="repro obs smoke report", store=store,
                        slo=slo, metrics=metrics, dropped=rec.dropped)
    results["obs_report_path"] = report
    results["obs_report_bytes"] = os.path.getsize(report)
    assert results["obs_report_bytes"] > 0, "empty ops report"
    print(snapshot_text(store=store, slo=slo, title="obs snapshot"))


def _collect_mesh_child(rec: Recorder, quick: bool, results: Dict):
    """Cross-process collection against a real second process: the mesh
    child records its own wave on 2 forced host devices and dumps it
    (JSONL + clock handshake); we rebase its events onto this process's
    perf_counter timeline and validate the merged Chrome trace."""
    child_path = "results/obs_child.events.jsonl"
    parent_hs = clock_handshake("parent")
    child = run_mesh_child("benchmarks.bench_obs", quick, devices=2,
                           trace_path=child_path)
    child_events, child_hs = read_stream(child_path)
    assert child_hs is not None, "child stream carried no clock handshake"
    assert len(child_events) == child["child_events"]
    merged = merge_streams(rec.events(), [(child_events, child_hs)],
                           parent_handshake=parent_hs)
    doc = write_chrome_trace(merged, "results/obs_merged.trace.json",
                             dropped=rec.dropped)
    counts = validate_chrome_trace(doc)
    assert counts["X"] > 0
    results["obs_child_events"] = len(child_events)
    results["obs_merged_events"] = len(merged)
    results["obs_merged_valid"] = 1
    results["obs_merged_trace_path"] = "results/obs_merged.trace.json"


def run(quick: bool = False) -> Dict:
    results: Dict = {}
    rec = Recorder()
    metrics = MetricsRegistry()
    _serve_half(rec, metrics, results)
    _fed_half(rec, metrics, results)

    paths = export_trace(rec, "results/obs_smoke")
    results["obs_events"] = paths["events"]
    results["obs_trace_path"] = paths["trace"]

    # lossless JSONL round-trip back to the in-memory event tuples
    back = read_jsonl(paths["jsonl"])
    assert back == rec.events(), "JSONL export did not round-trip"
    results["obs_jsonl_roundtrip"] = 1

    names = {e[1] for e in rec.events()}
    for want in ("submit", "serve.prefill_chunk", "serve.decode_step",
                 "finish", "fed.broadcast", "fed.collect", "fed.aggregate"):
        assert want in names, f"missing {want!r} events in the trace"
    results["obs_span_names_ok"] = 1
    results["obs_tracks"] = len({e[2] for e in rec.events()})

    _watch(rec, metrics, results)
    _collect_mesh_child(rec, quick, results)

    emit("obs/smoke", 0.0,
         f"{results['obs_events']} events on {results['obs_tracks']} "
         f"tracks -> {paths['trace']} (validated + round-tripped)")
    emit("obs/watch", 0.0,
         f"{results['obs_series']} series, slo_ok="
         f"{results['obs_slo_ok']}, report={results['obs_report_path']} "
         f"({results['obs_report_bytes']}B)")
    emit("obs/collect", 0.0,
         f"{results['obs_child_events']} child events rebased into "
         f"{results['obs_merged_events']}-event merged trace "
         f"(validated)")
    return results


def _mesh_child(quick: bool) -> None:
    """Child half of the collection section: record a tiny mesh-sharded
    wave, ``dump_stream`` it to ``$REPRO_CHILD_TRACE`` with a clock
    handshake, and print one MESH_RESULT line for the parent."""
    import json

    from benchmarks.common import MESH_RESULT_TAG
    from repro.launch.mesh import make_host_mesh
    from repro.obs import dump_stream

    rec = Recorder()
    metrics = MetricsRegistry()
    mesh = make_host_mesh(data=2)
    engine, prompts = _tiny_serve_engine(rec, metrics, mesh=mesh)
    for i in range(2):
        engine.submit(prompts[i], f"client{i}", max_new_tokens=4)
    engine.run()
    dump_stream(rec, os.environ["REPRO_CHILD_TRACE"],
                process="mesh_child")
    print(MESH_RESULT_TAG + json.dumps({
        "child_events": len(rec.events()),
        "child_devices": 2}), flush=True)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh-child", action="store_true")
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    if a.mesh_child:
        _mesh_child(a.quick)
    else:
        run(quick=a.quick)
