"""Observability layer tests: recorder semantics, exporter schema, and
the serve/fed instrumentation contracts.

Layers under test:

* ``repro.obs`` in isolation — recorder ring/clock semantics, the no-op
  null recorder, percentile/histogram math, JSONL round-trip, and the
  Chrome trace-event schema golden (``validate_chrome_trace`` over a
  synthetic document AND a real recorded run).
* The serve engine recorded end-to-end under page pressure — span
  coverage (prefill/decode/preempt/replay), TTFT/latency histograms,
  thin-view counter consistency (``trace_count`` & friends ARE registry
  counters now), page-allocator gauges, and — crucially — recording
  adding ZERO retraces (the paged engine still traces exactly twice).
* A ``FedSession`` recorded through broadcast → collect → aggregate →
  async flush — server spans in order, measured wire-byte counters
  matching ``comm_log``, and staleness accounting on the flush path.
* Spans in a running ``jax.profiler`` capture — from ``NULL_RECORDER``
  and a ``Recorder`` alike, nothing touched outside one — and a
  ``SyncRound`` round's span tree, read back from a CPU capture.
* The *watching* layer (PR 8) — streaming time-series bucketing
  (count/total conservation property-tested across bucket sizes,
  bounded memory via horizon eviction), SLO attainment/burn-rate math
  with its edge cases, per-class TTFT attainment on the engine,
  cross-process clock rebasing (synthetic AND a real
  subprocess child), ring-truncation surfacing in both exporters, and
  the HTML/terminal ops report.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_reduced
from repro.fed import AsyncConfig, FedSession, ServerConfig
from repro.models import model as model_lib
from repro.obs import (NULL_RECORDER, Histogram, MetricsRegistry,
                       NullRecorder, Objective, Recorder, SLOMonitor,
                       SLO_TRACK, SeriesStore, TimeSeries, chrome_trace,
                       clock_handshake, dump_stream, merge_streams,
                       percentile, read_jsonl, read_jsonl_with_meta,
                       read_stream, rebase_events, render_html,
                       snapshot_text, validate_chrome_trace,
                       write_chrome_trace, write_jsonl)
from repro.serve import AdapterRegistry, ServeEngine
from repro.serve.oracle import make_demo_adapter, merged_greedy

RANKS = (2, 4, 6, 8)
PROMPT_LEN = 6
STEPS = 10
PAGED_TRACES = 2   # one prefill trace + one decode trace (same as seed)


# ---------------------------------------------------------------------------
# recorder + metrics in isolation
# ---------------------------------------------------------------------------

def test_recorder_event_model():
    rec = Recorder()
    assert rec.enabled
    t0 = rec.now()
    rec.instant("mark", "trk", x=1)
    rec.complete("work", "trk", t0, rec.now(), n=2)
    with rec.span("outer", "other"):
        pass
    rec.counter_sample("bytes", "wire", 128)
    kinds = [e[0] for e in rec.events()]
    assert kinds == ["i", "X", "X", "C"]
    for kind, name, track, ts, dur, args in rec.events():
        assert isinstance(ts, float) and dur >= 0.0
    # counter samples carry {series: value} args
    assert rec.events()[-1][5] == {"bytes": 128}
    assert len(rec) == 4 and rec.appended == 4 and rec.dropped == 0
    rec.clear()
    assert len(rec) == 0 and rec.appended == 0


def test_recorder_ring_drops_oldest():
    rec = Recorder(capacity=4)
    for i in range(6):
        rec.instant(f"e{i}", "t")
    assert len(rec) == 4
    assert rec.appended == 6 and rec.dropped == 2
    assert [e[1] for e in rec.events()] == ["e2", "e3", "e4", "e5"]
    with pytest.raises(ValueError):
        Recorder(capacity=0)


def test_null_recorder_is_a_true_noop():
    assert isinstance(NULL_RECORDER, NullRecorder)
    assert not NULL_RECORDER.enabled
    NULL_RECORDER.instant("a", "t")
    NULL_RECORDER.complete("b", "t", 0.0, 1.0)
    NULL_RECORDER.counter_sample("c", "t", 1)
    with NULL_RECORDER.span("d", "t"):
        pass
    assert not hasattr(NULL_RECORDER, "annotation")
    assert len(NULL_RECORDER) == 0 and NULL_RECORDER.events() == []
    assert NULL_RECORDER.dropped == 0


def test_percentile_nearest_rank():
    xs = list(range(1, 101))          # 1..100
    assert percentile(xs, 50) == 50
    assert percentile(xs, 99) == 99
    assert percentile(xs, 100) == 100
    assert percentile(xs, 0) == 1
    assert percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_histogram_window_and_reset():
    h = Histogram("h", window=8)
    for v in range(100):
        h.observe(v)
    # lifetime stats cover everything; percentiles only the last window
    assert h.count == 100 and h.vmin == 0 and h.vmax == 99
    assert h.percentile(0) == 92.0     # window holds 92..99
    s = h.summary()
    assert s["count"] == 100 and s["p50"] == 95.0   # rank 4 of 92..99
    h.reset()
    assert h.count == 0 and h.summary() == {"count": 0}


def test_registry_get_or_create_and_export():
    m = MetricsRegistry()
    m.counter("a.c").inc(3)
    m.counter("a.c").inc()            # same object
    m.gauge("a.g").set(7)
    m.histogram("a.h").observe(1.5)
    assert m.has("a.c") and not m.has("nope")
    d = m.as_dict()
    assert d["a.c"] == 4 and d["a.g"] == 7 and d["a.h"]["count"] == 1
    text = m.summary_text("t")
    assert "a.c" in text and "a.h" in text


# ---------------------------------------------------------------------------
# Chrome trace-event schema golden (synthetic)
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_and_overlap_detection():
    rec = Recorder()
    t = rec.now()
    rec.complete("s1", "trk", t, t + 0.010)
    rec.complete("s2", "trk", t + 0.011, t + 0.020)
    rec.instant("i1", "trk")
    rec.counter_sample("series", "wire", 5)
    doc = chrome_trace(rec.events(), process_name="p")
    counts = validate_chrome_trace(doc)
    assert counts == {"X": 2, "i": 1, "C": 1, "M": 3, "dropped": 0}
    evs = doc["traceEvents"]
    # metadata rows: process name + one thread row per distinct track
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"p", "trk", "wire"}
    # earliest event is the time origin; everything is non-negative µs
    assert min(e["ts"] for e in evs if e["ph"] != "M") == 0.0
    # overlapping spans on one track must be rejected
    bad = Recorder()
    t = bad.now()
    bad.complete("a", "trk", t, t + 0.010)
    bad.complete("b", "trk", t + 0.005, t + 0.008)   # starts inside a
    with pytest.raises(AssertionError, match="overlap"):
        validate_chrome_trace(chrome_trace(bad.events()))


def test_jsonl_roundtrip(tmp_path):
    rec = Recorder()
    t = rec.now()
    rec.complete("s", "trk", t, t + 0.001, n=3, label="x")
    rec.instant("i", "trk")
    rec.counter_sample("c", "wire", 9)
    path = str(tmp_path / "events.jsonl")
    assert write_jsonl(rec.events(), path) == 3
    assert read_jsonl(path) == rec.events()


# ---------------------------------------------------------------------------
# serve engine, recorded end-to-end under page pressure
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    cfg = get_reduced("gemma-2b")
    key = jax.random.PRNGKey(0)
    params = model_lib.init_params(key, cfg)
    adapters = {
        f"client{i}": make_demo_adapter(jax.random.fold_in(key, 100 + i),
                                        cfg, r)
        for i, r in enumerate(RANKS)}
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 3), (8, PROMPT_LEN), 3, cfg.vocab_size))
    return cfg, params, adapters, prompts


@pytest.fixture(scope="module")
def recorded(serve_setup):
    """One recorded run, shared by the serve-side assertions below:
    8 requests squeezed through a 10-page pool (deferrals + preemptions
    guaranteed) with event recording on."""
    cfg, params, adapters, prompts = serve_setup
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    rec = Recorder()
    metrics = MetricsRegistry()
    engine = ServeEngine(params, cfg, reg, max_batch=8,
                         max_seq=PROMPT_LEN + STEPS, page_size=4,
                         num_pages=10, prefill_chunk=4,
                         recorder=rec, metrics=metrics)
    uids = [engine.submit(prompts[i], f"client{i % len(RANKS)}",
                          max_new_tokens=STEPS) for i in range(8)]
    outs = engine.run()
    return engine, rec, metrics, uids, outs


def test_recording_adds_zero_retraces_and_keeps_tokens_exact(
        serve_setup, recorded):
    cfg, params, adapters, prompts = serve_setup
    engine, rec, _, uids, outs = recorded
    assert engine.trace_count == PAGED_TRACES   # same constant as seed
    assert len(rec) > 0 and rec.dropped == 0
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % len(RANKS)}"], STEPS)
        np.testing.assert_array_equal(outs[uid], want)


def test_recorded_run_exports_valid_chrome_trace(recorded):
    """The golden test the ISSUE pins: a real engine run's trace is
    valid trace-event JSON with monotone non-overlapping spans per
    track."""
    engine, rec, _, uids, _ = recorded
    doc = chrome_trace(rec.events())
    counts = validate_chrome_trace(doc)
    assert counts["X"] > 0 and counts["i"] > 0
    names = {e[1] for e in rec.events()}
    for want in ("submit", "admit", "serve.prefill_chunk", "first_token",
                 "serve.decode_step", "finish", "defer", "preempt",
                 "replay"):
        assert want in names, f"missing {want!r} in the recorded trace"
    # one track per request plus the engine track
    tracks = {e[2] for e in rec.events()}
    assert f"{engine.name}/engine" in tracks
    for uid in uids:
        assert f"{engine.name}/{uid}" in tracks


def test_engine_counters_are_registry_views(recorded):
    """spec_stats()/trace_count/steps read THROUGH the registry: the
    public attributes and the metrics namespace can never disagree."""
    engine, _, metrics, _, _ = recorded
    views = {"traces": engine.trace_count, "steps": engine.steps,
             "tokens": engine.tokens_generated,
             "prefill_calls": engine.prefill_calls,
             "prefill_tokens": engine.prefill_tokens,
             "deferrals": engine.deferrals,
             "preemptions": engine.preemptions,
             "spec.dispatches": engine.spec_dispatches,
             "spec.drafted": engine.drafted_tokens,
             "spec.accepted": engine.accepted_tokens,
             "spec.rollback_pages": engine.rollback_pages}
    for suffix, attr_value in views.items():
        assert attr_value == metrics.counter(f"serve.{suffix}").value
    assert engine.bgmv_groups == metrics.gauge("serve.bgmv_groups").value
    stats = engine.spec_stats()
    assert stats["dispatches"] == engine.spec_dispatches
    # writable views still work (trace-time `self.trace_count += 1`)
    engine.trace_count += 1
    assert metrics.counter("serve.traces").value == PAGED_TRACES + 1
    engine.trace_count -= 1


def test_latency_histograms_and_ttft(recorded):
    engine, _, metrics, uids, _ = recorded
    ttft = metrics.histogram("serve.ttft_s")
    assert ttft.count == len(uids)        # one first token per request
    assert ttft.vmin > 0
    assert metrics.histogram("serve.request_s").count == len(uids)
    steps = metrics.histogram("serve.decode_step_s")
    assert steps.count == engine.steps
    s = steps.summary()
    assert 0 < s["p50"] <= s["p99"] <= s["max"]


def test_preemption_and_replay_are_visible(recorded):
    """The fixed invisibility: preempted requests leave preempt/replay
    instants, a replay-page counter, and per-request replay counts on
    their finish events."""
    engine, rec, metrics, _, _ = recorded
    assert engine.preemptions > 0 and engine.deferrals > 0
    events = rec.events()
    preempts = [e for e in events if e[1] == "preempt"]
    replays = [e for e in events if e[1] == "replay"]
    assert len(preempts) == engine.preemptions
    assert len(replays) == engine.preemptions   # every victim re-admits
    assert all(e[5]["pages_freed"] > 0 for e in preempts)
    assert metrics.counter("serve.replay_pages").value == sum(
        e[5]["pages_freed"] for e in preempts)
    finishes = [e for e in events if e[1] == "finish"]
    assert sum(e[5]["replays"] for e in finishes) == engine.preemptions


def test_page_allocator_gauges_and_conservation(recorded):
    engine, _, metrics, _, _ = recorded
    n = f"{engine.name}.pages.shard0"
    # drained pool: every page back on the free list, nothing owned
    assert metrics.gauge(f"{n}.free").value == engine.kv.pages_per_shard
    assert metrics.gauge(f"{n}.owners").value == 0
    assert metrics.gauge(f"{n}.pinned").value == 0
    allocs = metrics.counter(f"{n}.allocs").value
    extends = metrics.counter(f"{n}.extends").value
    freed = metrics.counter(f"{n}.freed").value
    truncated = metrics.counter(f"{n}.truncated").value
    assert allocs > 0 and extends > 0
    assert allocs + extends == freed + truncated   # page conservation


def test_default_engine_records_nothing(serve_setup):
    """No recorder passed => the no-op singleton, zero clock coupling."""
    cfg, params, adapters, prompts = serve_setup
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    engine = ServeEngine(params, cfg, reg, max_batch=2,
                         max_seq=PROMPT_LEN + 2)
    assert engine.rec is NULL_RECORDER
    uid = engine.submit(prompts[0], "client0", max_new_tokens=2)
    outs = engine.run()
    assert len(NULL_RECORDER) == 0
    assert engine.trace_count == PAGED_TRACES
    # no recorder => no timing state stamped into requests
    assert metricsless_histograms_empty(engine)
    assert outs[uid].size == 2


def metricsless_histograms_empty(engine) -> bool:
    for h in ("ttft_s", "request_s", "request_tok_s", "decode_step_s"):
        if engine.metrics.histogram(f"serve.{h}").count:
            return False
    return True


def test_two_engines_share_a_registry_without_clobbering(serve_setup):
    """Distinct engine names => disjoint metric namespaces: the second
    engine's construction must not zero the first engine's counters."""
    cfg, params, adapters, prompts = serve_setup
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    metrics = MetricsRegistry()
    a = ServeEngine(params, cfg, reg, max_batch=2, max_seq=PROMPT_LEN + 2,
                    metrics=metrics, name="a")
    a.submit(prompts[0], "client0", max_new_tokens=2)
    a.run()
    steps_a = a.steps
    assert steps_a > 0
    b = ServeEngine(params, cfg, reg, max_batch=2, max_seq=PROMPT_LEN + 2,
                    metrics=metrics, name="b")
    assert a.steps == steps_a          # b's __init__ zeroed only b.*
    assert b.steps == 0
    assert metrics.counter("a.steps").value == steps_a


# ---------------------------------------------------------------------------
# fed session, recorded through a server round + async flush
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fed_recorded():
    """A recorded server-side round (broadcast -> collect -> aggregate)
    plus an async flush with a forced-stale update."""
    cfg = get_reduced("roberta-large")
    scfg = ServerConfig(num_clients=4, clients_per_round=2,
                        strategy="hlora", rank_policy="random",
                        r_min=2, r_max=8, seed=0)
    base = model_lib.init_params(jax.random.PRNGKey(1), cfg)
    rec = Recorder()
    metrics = MetricsRegistry()
    sess = FedSession(cfg, scfg, base, recorder=rec, metrics=metrics,
                      acfg=AsyncConfig(max_staleness=2))
    cohort = sess.sample_cohort()
    stacked, heads = sess.broadcast_cohort(cohort)
    tree, up_heads = sess.collect_updates(cohort, stacked,
                                          heads if heads else None)
    sess.aggregate_round(tree, cohort, stacked_heads=up_heads)

    # async flush: one fresh update, one too stale (its start_version
    # predates a 5-merge jump in the server version)
    sl = {t: {k: np.asarray(v) for k, v in ad.items()}
          for t, ad in sess.global_lora.items()}
    stale = sess.make_update(1, sl, sess.version)
    sess.version += 5                       # stale's tau becomes 5 > 2
    fresh = sess.make_update(0, sl, sess.version)
    flags = sess.flush_async([fresh, stale])
    return sess, rec, metrics, cohort, flags


def test_fed_server_spans_in_order(fed_recorded):
    sess, rec, _, cohort, _ = fed_recorded
    server = [e for e in rec.events()
              if e[2] == "fed.server" and e[0] == "X"]
    names = [e[1] for e in server]
    assert names == ["fed.broadcast", "fed.collect", "fed.aggregate",
                     "fed.flush"]
    # sequential host code: already-sorted, non-overlapping
    for (_, _, _, a0, ad, _), (_, _, _, b0, _, _) in zip(server,
                                                         server[1:]):
        assert b0 >= a0 + ad
    assert server[0][5]["cohort"] == len(cohort)
    validate_chrome_trace(chrome_trace(rec.events()))


def test_fed_wire_bytes_counter_matches_comm_log(fed_recorded):
    sess, rec, metrics, _, _ = fed_recorded
    assert metrics.counter("fed.downlink_bytes").value == \
        sum(sess.comm_log["downlink"]) > 0
    assert metrics.counter("fed.uplink_bytes").value == \
        sum(sess.comm_log["uplink"]) > 0
    wire = [e for e in rec.events() if e[2] == "fed.wire"]
    assert wire and all(e[0] == "C" for e in wire)
    assert sum(e[5].get("fed.downlink_bytes", 0) for e in wire) == \
        sum(sess.comm_log["downlink"])
    assert metrics.counter("fed.rounds").value == sess.rounds_done == 1


def test_fed_flush_staleness_accounting(fed_recorded):
    sess, rec, metrics, _, flags = fed_recorded
    assert flags == [True, False]           # fresh merged, stale dropped
    assert metrics.counter("fed.updates_merged").value == 1
    assert metrics.counter("fed.updates_dropped").value == 1
    stale_h = metrics.histogram("fed.staleness")
    assert stale_h.count == 2 and stale_h.vmax == 5
    flush = [e for e in rec.events() if e[1] == "fed.flush"]
    assert len(flush) == 1 and flush[0][5]["merged"] == 1


def test_fed_default_session_records_nothing():
    cfg = get_reduced("roberta-large")
    scfg = ServerConfig(num_clients=2, clients_per_round=2, seed=0)
    base = model_lib.init_params(jax.random.PRNGKey(2), cfg)
    sess = FedSession(cfg, scfg, base)
    assert sess.rec is NULL_RECORDER
    sess.broadcast_cohort(np.array([0, 1]))
    assert len(NULL_RECORDER) == 0
    # metrics stay on regardless: wire bytes still counted
    assert sess.metrics.counter("fed.downlink_bytes").value == \
        sum(sess.comm_log["downlink"]) > 0


# ---------------------------------------------------------------------------
# spans in a running profiler capture
# ---------------------------------------------------------------------------

def _capture_host_spans(tmp_path, fn):
    """Run ``fn`` under a CPU ``jax.profiler`` capture; the (name, start,
    end) of every event on the capture's python host lines."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    paths = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                             recursive=True))
    pd = ProfileData.from_file(paths[-1])
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines if line.name.startswith("python")
            for ev in line.events]


def _disabled_recorder():
    rec = Recorder()
    rec.enabled = False
    return rec


@pytest.mark.parametrize("make", [lambda: NULL_RECORDER, Recorder],
                         ids=["null", "recorder"])
def test_span_reaches_a_running_capture(tmp_path, make):
    rec = make()

    def work():
        with rec.span("fed.broadcast", "fed.server", cohort=2):
            pass
    spans = _capture_host_spans(tmp_path, work)
    assert [n for n, _, _ in spans if n.startswith("fed.")] == \
        ["fed.broadcast"]
    assert [e[1] for e in rec.events()] == \
        (["fed.broadcast"] if rec.enabled else [])


@pytest.mark.parametrize("make", [lambda: NULL_RECORDER,
                                  _disabled_recorder],
                         ids=["null", "disabled"])
def test_span_outside_a_capture_touches_nothing(monkeypatch, make):
    """No capture running and recording off: one flag check, the shared
    null context back, no annotation opened, nothing in the ring."""
    from repro.obs import recorder as recorder_mod

    def refuse(name):
        raise AssertionError(f"annotation {name!r} with no capture")
    monkeypatch.setattr(recorder_mod, "TraceAnnotation", refuse)
    rec = make()
    ctx = rec.span("fed.broadcast", "fed.server", cohort=2)
    assert ctx is recorder_mod._NULL_CTX
    with ctx:
        pass
    assert len(rec) == 0 and rec.events() == [] and rec.appended == 0


def test_recorder_span_outside_a_capture_fills_only_the_ring(
        monkeypatch):
    from repro.obs import recorder as recorder_mod

    def refuse(name):
        raise AssertionError(f"annotation {name!r} with no capture")
    monkeypatch.setattr(recorder_mod, "TraceAnnotation", refuse)
    rec = Recorder()
    with rec.span("fed.collect", "fed.server", cohort=3) as sp:
        pass
    (kind, name, track, t0, dur, args), = rec.events()
    assert (kind, name, track, args) == ("X", "fed.collect", "fed.server",
                                         {"cohort": 3})
    assert dur == sp.seconds >= 0.0


#: the span tree of one SyncRound round (fed/schedulers.py)
ROUND_TREE = ("fed.round", [
    ("fed.broadcast", [("fed.redistribute", []), ("fed.downlink", []),
                       ("fed.restack", [])]),
    ("fed.data", []),
    ("fed.train", []),
    ("fed.collect", [("fed.uplink", []), ("fed.restack", [])]),
    ("fed.aggregate", []),
    ("fed.close", [])])


def _nest(spans):
    """(name, start, end) spans nested by containment, in start order:
    a list of (name, [children])."""
    roots, stack = [], []
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and not (stack[-1][0] <= s and e <= stack[-1][1]):
            stack.pop()
        node = (name, [])
        (stack[-1][2] if stack else roots).append(node)
        stack.append((s, e, node[1]))
    return roots


def test_sync_round_span_tree_in_a_capture(tmp_path):
    """One reduced-width round under a CPU capture: the round's phases,
    in order and properly nested, on the capture's host line and in the
    ring buffer (one track per level, so the Chrome export validates)."""
    from repro.fed import SyncRound
    cfg = get_reduced("roberta-large")
    scfg = ServerConfig(num_clients=4, clients_per_round=2,
                        strategy="hlora", rank_policy="random",
                        r_min=2, r_max=8, seed=0)
    base = model_lib.init_params(jax.random.PRNGKey(5), cfg)
    rec = Recorder()
    sess = FedSession(cfg, scfg, base, recorder=rec)

    def train(frozen, trainable, masks, batches):
        return trainable, np.zeros(len(batches), np.float32)

    def data_fn(cohort, rnd):
        return np.asarray(cohort)
    SyncRound().run(sess, train, data_fn, 1)     # compiles the merge
    rec.clear()
    spans = _capture_host_spans(
        tmp_path, lambda: SyncRound().run(sess, train, data_fn, 1))
    assert _nest([s for s in spans if s[0].startswith("fed.")]) == \
        [ROUND_TREE]
    xs = [e for e in rec.events() if e[0] == "X"]
    assert _nest([(e[1], e[3], e[3] + e[4]) for e in xs]) == [ROUND_TREE]
    tracks = {e[1]: e[2] for e in xs}
    assert (tracks["fed.round"], tracks["fed.train"],
            tracks["fed.uplink"]) == ("fed.rounds", "fed.server",
                                      "fed.server.parts")
    assert [e[5] for e in xs if e[1] == "fed.round"] == [{"round": 1}]
    validate_chrome_trace(chrome_trace(rec.events()))


# ---------------------------------------------------------------------------
# clock-discipline lint: obs owns the clock inside serve + fed
# ---------------------------------------------------------------------------

def test_no_raw_clock_reads_in_serve_fed_or_obs():
    """A raw ``time.time()``/``time.perf_counter()`` call inside
    repro/serve, repro/fed, or repro/obs would fork the timeline off the
    recorder's shared clock — every timestamp must come from
    ``Recorder.now()`` (and the one sanctioned wall-clock read for the
    cross-process handshake is ``Recorder.wall()``, which lives in the
    allowlisted clock owner ``obs/recorder.py``). Enforced by the
    AST-accurate ``clock-discipline`` pass (real call sites only — the
    grep this replaced counted docstring mentions and missed aliased
    imports); the whole-tree run incl. the other rules is pinned in
    test_system.py."""
    from repro.analysis import run_paths
    root = os.path.join(os.path.dirname(__file__), os.pardir,
                        "src", "repro")
    paths = [os.path.join(root, sub) for sub in ("serve", "fed", "obs")]
    findings = run_paths(paths, rules=["clock-discipline"])
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# streaming time series: bucketing conservation + bounded memory
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(n=st.integers(min_value=1, max_value=64),
       bucket_ms=st.sampled_from([1, 5, 25, 100, 1000]),
       spread_s=st.floats(min_value=0.001, max_value=5.0),
       valued=st.booleans())
def test_timeseries_bucketing_conserves_mass(n, bucket_ms, spread_s,
                                             valued):
    """Property (the module docstring's invariant): for ANY bucket
    width, as long as nothing is evicted, sum of bucket counts == number
    of observations and sum of bucket totals == sum of values —
    rebucketing conserves mass."""
    rng = np.random.default_rng(n * 1000 + bucket_ms)
    ts = rng.uniform(0.0, spread_s, size=n)
    vals = rng.uniform(-10.0, 10.0, size=n) if valued else None
    s = TimeSeries("s", bucket_s=bucket_ms / 1e3, max_buckets=1 << 24)
    for i in range(n):
        s.observe(float(ts[i]), None if vals is None else float(vals[i]))
    assert s.count == n and s.dropped == 0
    assert s.window_count() == sum(b.count for b in s.buckets()) == n
    want_total = 0.0 if vals is None else float(np.sum(vals))
    assert s.window_total() == pytest.approx(want_total, abs=1e-9)
    assert s.total == pytest.approx(want_total, abs=1e-9)
    # buckets are disjoint, sorted, and every observation's bucket start
    # is at or before its timestamp
    starts = [b.start for b in s.buckets()]
    assert starts == sorted(starts) and len(set(starts)) == len(starts)


def test_timeseries_bounded_memory_and_eviction():
    """Advancing time past the window evicts oldest buckets into
    ``dropped``; late observations behind the horizon never resurrect
    them. Lifetime count keeps covering everything."""
    s = TimeSeries("s", bucket_s=1.0, max_buckets=4)
    for t in range(10):                    # buckets 0..9, window keeps 4
        s.observe(t + 0.5, 1.0)
    assert len(s) <= 4
    assert s.count == 10
    assert s.window_count() + s.dropped == 10
    assert s.dropped == 6
    retained = {b.start for b in s.buckets()}
    assert retained == {6.0, 7.0, 8.0, 9.0}
    s.observe(0.5, 1.0)                    # behind the horizon: dropped
    assert s.dropped == 7 and len(s) <= 4 and s.count == 11
    with pytest.raises(ValueError):
        TimeSeries("s", bucket_s=0.0)
    with pytest.raises(ValueError):
        TimeSeries("s", max_buckets=0)


def test_seriesstore_fold_routing():
    """C samples -> valued series; X spans -> span.<name> durations;
    instants -> count-only inst.<name> plus the stamped-value series
    for the instrumented names (first_token.ttft_s etc.)."""
    rec = Recorder()
    t = rec.now()
    rec.counter_sample("fed.downlink_bytes", "fed.wire", 256)
    rec.complete("decode_step", "serve/engine", t, t + 0.010, batch=3)
    rec.instant("first_token", "serve/req0", ttft_s=0.125)
    rec.instant("admit", "serve/req0")      # no valued routing
    store = SeriesStore(bucket_s=1.0)
    n = store.fold(rec.events())
    assert n == 5                           # C + X + (inst + valued) + inst
    assert store.series("fed.downlink_bytes").total == 256.0
    sp = store.series("span.decode_step")
    assert sp.count == 1 and sp.total == pytest.approx(0.010)
    assert store.series("first_token.ttft_s").total == \
        pytest.approx(0.125)
    assert store.series("inst.admit").count == 1
    assert not store.has("admit.ttft_s")
    d = store.as_dict()
    assert d["first_token.ttft_s"]["mean"] == pytest.approx(0.125)


def test_seriesstore_gauge_sampling():
    m = MetricsRegistry()
    m.gauge("pool.free").set(7)
    m.gauge("pool.owners").set(2)
    store = SeriesStore(bucket_s=1.0)
    assert store.sample_gauges(m, t=1.5) == 2
    assert store.sample_gauges(m, t=2.5, prefix="pool.free") == 1
    assert store.series("pool.free").count == 2
    assert store.series("pool.owners").count == 1


# ---------------------------------------------------------------------------
# SLO monitor: attainment / burn-rate math + violation instants
# ---------------------------------------------------------------------------

def test_slo_attainment_and_violation_instants():
    rec = Recorder()
    t = rec.now()
    for i, ttft in enumerate((0.05, 0.08, 0.50, 0.06)):
        rec.instant("first_token", f"serve/req{i}", ttft_s=ttft)
    slo = SLOMonitor([Objective("ttft", series="first_token.ttft_s",
                                threshold=0.1, target=0.9)],
                     recorder=rec)
    assert slo.fold(rec.events()) == 4
    states = slo.evaluate(now=t + 1.0)
    st_ = states["ttft"]
    assert st_.good == 3 and st_.bad == 1
    assert st_.attainment == pytest.approx(0.75)
    assert st_.error_budget == pytest.approx(0.1)
    assert st_.burn_rate == pytest.approx(2.5)      # 25% bad / 10% budget
    assert st_.in_violation
    # violation recorded both in the log and on the obs.slo track
    assert len(slo.violations) == 1
    assert slo.violations[0]["objective"] == "ttft"
    viol = [e for e in rec.events() if e[2] == SLO_TRACK]
    assert len(viol) == 1 and viol[0][1] == "slo_violation.ttft"
    assert viol[0][5]["attainment"] == pytest.approx(0.75)


def test_slo_edge_cases_empty_and_all_violating():
    """Empty window: vacuously attained, zero burn. All-violating:
    attainment 0 and burn at the 1/(1-target) ceiling."""
    slo = SLOMonitor([Objective("o", series="s", threshold=1.0,
                                target=0.99)])
    st_ = slo.evaluate(now=0.0)["o"]
    assert st_.total == 0 and st_.attainment == 1.0
    assert st_.burn_rate == 0.0 and not st_.in_violation
    for i in range(5):
        slo.observe("s", float(i) * 0.1, 2.0)       # all above threshold
    st_ = slo.evaluate(now=1.0)["o"]
    assert st_.attainment == 0.0 and st_.in_violation
    assert st_.burn_rate == pytest.approx(1.0 / (1.0 - 0.99))
    # duplicate objective names are rejected; target 1.0 has no budget
    with pytest.raises(ValueError):
        SLOMonitor([Objective("x", series="a", threshold=1),
                    Objective("x", series="b", threshold=1)])
    with pytest.raises(ValueError):
        Objective("y", series="a", threshold=1, target=1.0)


def test_slo_higher_is_better_and_count_only_skip():
    slo = SLOMonitor([Objective("tput", series="tok_s", threshold=100.0,
                                target=0.5, lower_is_better=False)])
    rec = Recorder()
    rec.instant("admit", "t")               # count-only: not routed
    assert slo.fold(rec.events()) == 0
    slo.observe("tok_s", 0.1, 150.0)
    slo.observe("tok_s", 0.2, 50.0)
    slo.observe("tok_s", 0.3, 120.0)
    st_ = slo.evaluate(now=1.0)["tput"]
    assert st_.good == 2 and st_.bad == 1 and not st_.in_violation


def test_engine_slo_classes_attainment(serve_setup):
    """``submit(slo_class=...)`` carries the class through the request
    track; per-class TTFT attainment settles at first token — a
    sub-nanosecond target forces a miss (attainment 0.0 + an
    ``slo_miss`` instant on obs.slo), a generous one attains 1.0."""
    cfg, params, adapters, prompts = serve_setup
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    rec = Recorder()
    metrics = MetricsRegistry()
    engine = ServeEngine(params, cfg, reg, max_batch=2,
                         max_seq=PROMPT_LEN + 2, recorder=rec,
                         metrics=metrics,
                         slo_ttft_s={"fast": 1e-12, "easy": 600.0})
    engine.submit(prompts[0], "client0", max_new_tokens=2,
                  slo_class="fast")
    engine.submit(prompts[1], "client1", max_new_tokens=2,
                  slo_class="easy")
    engine.run()
    assert engine.slo_attainment() == {"easy": 1.0, "fast": 0.0}
    assert metrics.counter("serve.slo.fast.total").value == 1
    assert metrics.counter("serve.slo.fast.ok").value == 0
    assert metrics.counter("serve.slo.easy.ok").value == 1
    misses = [e for e in rec.events()
              if e[1] == "slo_miss" and e[2] == SLO_TRACK]
    assert len(misses) == 1 and misses[0][5]["cls"] == "fast"
    # the submit instant carries the class for the trace
    submits = [e for e in rec.events() if e[1] == "submit"]
    assert {e[5].get("slo_class") for e in submits} == {"fast", "easy"}
    # per-class TTFT histogram populated alongside the aggregate one
    assert metrics.histogram("serve.ttft_s.fast").count == 1


def test_engine_slo_classes_inert_without_recorder(serve_setup):
    """Recording off => no TTFT clock => the class accounting must not
    move (and must not crash): observe-only means a production engine
    with recording disabled stays a true no-op."""
    cfg, params, adapters, prompts = serve_setup
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    engine = ServeEngine(params, cfg, reg, max_batch=2,
                         max_seq=PROMPT_LEN + 2,
                         slo_ttft_s={"fast": 1e-12})
    engine.submit(prompts[0], "client0", max_new_tokens=2,
                  slo_class="fast")
    engine.run()
    assert engine.slo_attainment() == {}
    assert engine.metrics.counter("serve.slo.fast.total").value == 0


# ---------------------------------------------------------------------------
# cross-process collection: clock rebase + merge (synthetic and real)
# ---------------------------------------------------------------------------

def test_rebase_events_constant_shift_preserves_timing():
    """Synthetic two-process streams: the rebase is one constant shift
    per child — child-internal gaps and span durations are exact, and
    per-track ordering survives."""
    child_events = [
        ("X", "a", "trk", 10.0, 0.5, {}),
        ("X", "b", "trk", 11.0, 0.25, {}),
        ("i", "m", "trk", 12.0, 0.0, {}),
    ]
    # child perf origin ~10s, parent ~1000s, shared wall clock 5000s
    child_hs = {"process": "kid", "perf": 10.0, "wall": 5000.0}
    parent_hs = {"process": "parent", "perf": 1000.0, "wall": 5000.0}
    out = rebase_events(child_events, child_hs, parent_hs,
                        track_prefix="kid/")
    # offset = (5000-10) - (5000-1000) = 990
    assert [e[3] for e in out] == [1000.0, 1001.0, 1002.0]
    assert [e[4] for e in out] == [0.5, 0.25, 0.0]
    assert all(e[2] == "kid/trk" for e in out)
    # internal gap conserved exactly
    assert out[1][3] - out[0][3] == child_events[1][3] - child_events[0][3]


def test_merge_streams_monotone_and_valid():
    parent = [("X", "p", "ptrk", 1000.0, 0.5, {}),
              ("X", "q", "ptrk", 1002.0, 0.5, {})]
    child = [("X", "c1", "trk", 10.0, 0.2, {}),
             ("X", "c2", "trk", 10.5, 0.2, {})]
    child_hs = {"process": "kid", "perf": 9.0, "wall": 5000.0}
    # child perf 9.0 == parent perf 1000.5 on the shared wall clock
    parent_hs = {"process": "parent", "perf": 1000.5, "wall": 5000.0}
    merged = merge_streams(parent, [(child, child_hs)], parent_hs)
    assert [e[3] for e in merged] == sorted(e[3] for e in merged)
    # child events landed between the parent spans
    kid = [e for e in merged if e[2] == "kid/trk"]
    assert kid[0][3] == pytest.approx(1001.5)
    validate_chrome_trace(chrome_trace(merged))
    # a handshake-less child is rejected, not silently misaligned
    with pytest.raises(ValueError, match="handshake"):
        merge_streams(parent, [(child, None)], parent_hs)


def test_collect_roundtrip_with_real_child_process(tmp_path):
    """The golden collection test: a REAL subprocess records events,
    ``dump_stream``s them, and the parent merges them onto its own
    timeline — the child's events must land between the parent's
    before/after markers and the merged trace must validate."""
    path = str(tmp_path / "child.jsonl")
    src_root = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    child_code = (
        "from repro.obs import Recorder, dump_stream\n"
        "rec = Recorder()\n"
        "t0 = rec.now()\n"
        "rec.complete('child_work', 'work', t0, rec.now(), n=1)\n"
        "rec.instant('child_mark', 'work')\n"
        f"dump_stream(rec, {path!r}, process='kid')\n")
    rec = Recorder()
    rec.instant("before_child", "parent")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [src_root] + ([env["PYTHONPATH"]]
                      if env.get("PYTHONPATH") else []))
    subprocess.run([sys.executable, "-c", child_code], env=env,
                   check=True, timeout=120)
    rec.instant("after_child", "parent")
    events, hs = read_stream(path)
    assert hs is not None and hs["process"] == "kid"
    assert hs["dropped"] == 0
    assert [e[1] for e in events] == ["child_work", "child_mark"]
    merged = merge_streams(rec.events(), [(events, hs)],
                           clock_handshake("parent"))
    t_before = next(e[3] for e in merged if e[1] == "before_child")
    t_after = next(e[3] for e in merged if e[1] == "after_child")
    kid = [e for e in merged if e[2].startswith("kid/")]
    assert len(kid) == 2
    for e in kid:
        assert t_before < e[3] < t_after
    counts = validate_chrome_trace(chrome_trace(merged))
    assert counts["X"] == 1 and counts["i"] == 3


# ---------------------------------------------------------------------------
# exporters: ring truncation surfaced, meta rows, atomic writes
# ---------------------------------------------------------------------------

def test_ring_truncation_surfaces_in_both_exporters(tmp_path):
    """A small-capacity ring that dropped events must say so in both
    export formats — a trace that silently starts mid-run reads as a
    complete record."""
    rec = Recorder(capacity=3)
    for i in range(8):
        rec.instant(f"e{i}", "t")
    assert rec.dropped == 5
    trace_path = str(tmp_path / "t.trace.json")
    doc = write_chrome_trace(rec.events(), trace_path,
                             dropped=rec.dropped)
    counts = validate_chrome_trace(doc)
    assert counts["dropped"] == 5
    with open(trace_path) as f:
        assert json.load(f)["traceEvents"]
    jsonl_path = str(tmp_path / "t.events.jsonl")
    n = write_jsonl(rec.events(), jsonl_path,
                    meta={"dropped": rec.dropped})
    assert n == 3
    events, meta = read_jsonl_with_meta(jsonl_path)
    assert meta == {"dropped": 5}
    assert events == rec.events()          # retained events round-trip
    assert read_jsonl(jsonl_path) == rec.events()   # meta row skipped


def test_write_jsonl_without_meta_has_no_meta_row(tmp_path):
    rec = Recorder()
    rec.instant("e", "t")
    path = str(tmp_path / "plain.jsonl")
    write_jsonl(rec.events(), path)
    events, meta = read_jsonl_with_meta(path)
    assert meta is None and events == rec.events()
    with open(path) as f:
        assert len(f.read().strip().splitlines()) == 1


def test_exporter_writes_are_atomic(tmp_path):
    """No ``*.tmp.*`` leftovers after a write, and the destination
    appears fully formed (the tmp+os.replace discipline)."""
    rec = Recorder()
    t = rec.now()
    rec.complete("s", "t", t, t + 0.001)
    for fn, path in ((write_jsonl, tmp_path / "a.jsonl"),
                     (write_chrome_trace, tmp_path / "a.json")):
        fn(rec.events(), str(path))
        assert path.exists()
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


# ---------------------------------------------------------------------------
# ops report: HTML + terminal snapshot
# ---------------------------------------------------------------------------

def test_report_html_and_snapshot(tmp_path):
    rec = Recorder()
    t = rec.now()
    rec.instant("first_token", "serve/req0", ttft_s=0.05)
    rec.instant("first_token", "serve/req1", ttft_s=5.0)
    rec.complete("decode_step", "serve/engine", t, t + 0.01)
    store = SeriesStore(bucket_s=0.5)
    store.fold(rec.events())
    slo = SLOMonitor([Objective("ttft", series="first_token.ttft_s",
                                threshold=0.1, target=0.9)])
    slo.fold(rec.events())
    m = MetricsRegistry()
    m.counter("serve.tokens").inc(42)
    html = render_html(title="t&t", store=store, slo=slo, metrics=m,
                       dropped=3)
    assert "t&amp;t" in html                # escaping
    assert "VIOLATED" in html and "burn" in html
    assert "<svg" in html and "polyline" in html    # sparklines
    assert "dropped" in html and ">3</b>" in html   # truncation banner
    assert "serve.tokens" in html
    from repro.obs import write_html
    p = write_html(str(tmp_path / "r.html"), store=store, slo=slo)
    assert os.path.getsize(p) > 0
    assert not [q for q in os.listdir(tmp_path) if ".tmp." in q]
    txt = snapshot_text(store=store, slo=slo, metrics=m, title="snap")
    assert "snap" in txt and "VIOLATED" in txt
    assert "first_token.ttft_s" in txt and "serve.tokens" in txt


def test_report_empty_inputs_render():
    html = render_html()
    assert "<html" in html and "SLO" not in html
    assert snapshot_text() == ""
    from repro.obs import sparkline_svg
    assert "no data" in sparkline_svg([])
    assert "polyline" in sparkline_svg([1.0])       # single point ok
