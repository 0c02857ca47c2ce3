"""Serving example: continuous-batched decode with per-request LoRA over
a paged KV cache.

The HLoRA server produces per-client, heterogeneous-rank adapters; at
deployment each request carries its own (the federated client's
personalized one). This example drives ``repro.serve``: four adapters
with ranks 2/4/6/8 go into an AdapterRegistry slab, eight requests
spread across them run through one jitted ServeEngine step (the S-LoRA
trade: factored adapters gathered per-row, no merge), and the output is
checked token-for-token against per-request merged-weight decoding.
Mid-run one adapter is hot-swapped to show the retrace counter stays
flat.

The second scenario oversubscribes the page pool with long-prompt
traffic: more concurrent requests than a dense ring cache of the same
memory could ever admit. Page-gated admission lets actual usage — not
``max_seq`` — decide concurrency; requests the pool cannot hold yet are
*deferred* in the queue and finish once earlier rows release pages.

The third scenario turns on lossless speculative decode for replayed
traffic: a scripted drafter proposes the request's previous answer, one
multi-token verify dispatch scores the whole window, and rejected
suffixes roll their KV pages back — same tokens as plain decode, a
fraction of the dispatches.

The fourth scenario reruns both engine flavours with event recording on
and exports the shared timeline as a perfetto-loadable Chrome trace plus
a JSONL event archive, then folds the same events into time series and
per-class TTFT SLOs and renders the static HTML ops report
(see ``src/repro/obs``).

  PYTHONPATH=src python examples/serve_adapters.py
"""
import os
import time

import jax
import numpy as np

from repro.configs import get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.obs import (MetricsRegistry, Objective, Recorder, SLOMonitor,
                       SeriesStore, snapshot_text, validate_chrome_trace,
                       write_chrome_trace, write_html, write_jsonl)
from repro.serve import AdapterRegistry, ScriptedDrafter, ServeEngine
from repro.serve.oracle import make_demo_adapter, merged_greedy

STEPS = 16
PROMPT_LEN = 8


def _fixture():
    cfg = get_reduced("gemma-2b")
    key = jax.random.PRNGKey(0)
    params = model_lib.init_params(key, cfg)

    ranks = [2, 4, 6, 8]
    adapters = {f"client{i}": make_demo_adapter(
                    jax.random.fold_in(key, 100 + i), cfg, r)
                for i, r in enumerate(ranks)}
    registry = AdapterRegistry(cfg, capacity=len(ranks))
    for aid, tree in adapters.items():
        registry.register(aid, tree)
    return cfg, key, params, ranks, adapters, registry


def main():
    enable_compile_cache()
    cfg, key, params, ranks, adapters, registry = _fixture()

    engine = ServeEngine(params, cfg, registry, max_batch=8,
                         max_seq=PROMPT_LEN + STEPS)
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 3), (8, PROMPT_LEN), 3, cfg.vocab_size))
    uids = [engine.submit(prompts[i], f"client{i % len(ranks)}",
                          max_new_tokens=STEPS) for i in range(8)]

    t0 = time.time()
    outs = engine.run()
    t_engine = time.time() - t0
    traces_before = engine.trace_count
    steps_first = engine.steps

    t0 = time.time()
    oracles = [merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % len(ranks)}"], STEPS)
               for i in range(8)]
    t_merged = time.time() - t0

    # hot-swap client1's adapter mid-deployment: value-only slab write
    for t in adapters["client1"]:
        adapters["client1"][t]["B"] = adapters["client1"][t]["B"] * 1.5
    registry.refresh("client1")
    engine.submit(prompts[0], "client1", max_new_tokens=4)
    engine.run()
    swap_retraces = engine.trace_count - traces_before

    match = sum(int((outs[u] == o).all()) for u, o in zip(uids, oracles))
    total_tok = 8 * STEPS
    print(f"batched multi-LoRA engine: {t_engine:.2f}s for 8 req × "
          f"{STEPS} tokens ({total_tok / t_engine:.0f} tok/s), "
          f"{steps_first} steps, traces={traces_before}")
    print(f"merged per-request oracle: {t_merged:.2f}s")
    print(f"greedy outputs exactly match oracle: {match}/8")
    print(f"hot-swap retraces: {swap_retraces} (expect 0)")
    print("tokens (req 0):", outs[uids[0]].tolist())


def oversubscribed():
    """Long prompts against a deliberately small page pool.

    12 requests of 48+8 = 56 tokens each (7 pages at page_size 8) share a
    24-page pool: at most 3 requests fit at once. A dense ring cache
    spending the same memory (24*8 = 192 slots at max_seq 56) would hold
    only 3 rows *ever* — here all 12 batch rows exist, admission simply
    waits for pages, and every deferred request still finishes with
    oracle-exact greedy tokens.
    """
    cfg, key, params, ranks, adapters, registry = _fixture()
    num_req, prompt_len, steps, ps, num_pages = 12, 48, 8, 8, 24
    engine = ServeEngine(params, cfg, registry, max_batch=num_req,
                         max_seq=prompt_len + steps, page_size=ps,
                         num_pages=num_pages, prefill_chunk=16)
    dense_rows_same_memory = (num_pages * ps) // (prompt_len + steps)
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 5), (num_req, prompt_len), 3,
        cfg.vocab_size))
    uids = [engine.submit(prompts[i], f"client{i % len(ranks)}",
                          max_new_tokens=steps) for i in range(num_req)]
    t0 = time.time()
    outs = engine.run()
    t = time.time() - t0
    engine.kv.allocator.check()
    match = sum(
        int((outs[uids[i]] == merged_greedy(
            params, cfg, prompts[i], adapters[f"client{i % len(ranks)}"],
            steps)).all())
        for i in range(num_req))
    pool_kb = engine.kv_cache_bytes() / 1024
    print(f"\noversubscribed: {num_req} req x {prompt_len + steps} tok "
          f"through a {num_pages}-page pool ({pool_kb:.0f} KiB KV) in "
          f"{t:.2f}s")
    print(f"  dense ring of equal memory admits {dense_rows_same_memory} "
          f"concurrent rows; the pool served all {num_req} "
          f"({engine.deferrals} deferrals, {engine.preemptions} "
          f"preemptions, traces={engine.trace_count})")
    print(f"  greedy outputs exactly match oracle: {match}/{num_req}")


def speculative():
    """Lossless draft–verify decode on replayed traffic.

    A common serving pattern: the same request comes back (a regenerate
    click, a retried call, a cache-warmed template) and its previous
    answer is a near-perfect draft. The drafter scripts the prior
    output, one verify dispatch scores all ``spec_k + 1`` positions, and
    every dispatch commits the whole accepted window — decode dispatches
    drop by ~(spec_k+1)x at acceptance 1. Acceptance is *exact greedy
    token-match*, so even a garbage draft (cold n-gram lookup, changed
    adapter) only costs speed: the output is guaranteed byte-identical
    to plain decode, and rejected suffixes roll their KV pages back into
    the pool.
    """
    cfg, key, params, ranks, adapters, registry = _fixture()
    num_req, steps, spec_k = 8, 16, 4
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 9), (num_req, 8), 3, cfg.vocab_size))

    outs, times = {}, {}
    drafter = ScriptedDrafter()
    for name, dr in (("plain", None), ("replay", drafter)):
        engine = ServeEngine(params, cfg, registry, max_batch=num_req,
                             max_seq=prompts.shape[1] + steps,
                             drafter=dr, spec_k=spec_k)

        def wave():
            uids = [engine.submit(prompts[i], f"client{i % len(ranks)}",
                                  max_new_tokens=steps)
                    for i in range(num_req)]
            if dr is not None:       # draft from the previous answers
                for u, prev in zip(uids, outs["plain"]):
                    drafter.set(u, prev)
            t0 = time.time()
            done = engine.run()
            return time.time() - t0, [done[u] for u in uids]

        wave()                                       # warmup compile
        before = (engine.spec_dispatches, engine.drafted_tokens,
                  engine.accepted_tokens, engine.rollback_pages)
        times[name], outs[name] = wave()
    # stats of the *timed* wave only — counters accumulate across waves
    dispatches, drafted, accepted, rollbacks = (
        engine.spec_dispatches - before[0],
        engine.drafted_tokens - before[1],
        engine.accepted_tokens - before[2],
        engine.rollback_pages - before[3])
    exact = sum(int((a == b).all())
                for a, b in zip(outs["replay"], outs["plain"]))
    total = num_req * steps
    print(f"\nspeculative replay: {total} tokens plain "
          f"{times['plain']:.2f}s ({total / times['plain']:.0f} tok/s) "
          f"vs draft-verify {times['replay']:.2f}s "
          f"({total / times['replay']:.0f} tok/s, "
          f"{times['plain'] / times['replay']:.2f}x)")
    print(f"  acceptance {accepted / max(drafted, 1):.2f} over "
          f"{dispatches} dispatches, "
          f"{rollbacks} pages rolled back, "
          f"byte-identical to plain: {exact}/{num_req}")


def observability():
    """Record a full serving timeline and export it for perfetto.

    Two engines share ONE recorder, each under its own track prefix: a
    plain engine squeezed through a deliberately small page pool (so the
    trace shows deferrals, preemptions, and replays alongside the
    prefill/decode spans) and a speculative engine replaying the first
    engine's answers through draft–verify (draft and verify spans on its
    engine track). The result drops straight into ``ui.perfetto.dev``:

      results/serve_trace.json    Chrome trace-event JSON (validated)
      results/serve_events.jsonl  lossless per-event archive
      results/serve_report.html   static ops report (series sparklines,
                                  SLO attainment, metrics summary)
    """
    cfg, key, params, ranks, adapters, registry = _fixture()
    rec = Recorder()
    metrics = MetricsRegistry()

    # plain engine, tight pool: 8 req x 24 tok through 10 pages of 4;
    # two SLO classes with generous TTFT ceilings — the report's
    # attainment table is the point, not a perf gate
    engine = ServeEngine(params, cfg, registry, max_batch=8,
                         max_seq=PROMPT_LEN + STEPS, page_size=4,
                         num_pages=10, prefill_chunk=4,
                         recorder=rec, metrics=metrics, name="serve",
                         slo_ttft_s={"interactive": 60.0, "batch": 600.0})
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 7), (8, PROMPT_LEN), 3, cfg.vocab_size))
    uids = [engine.submit(prompts[i], f"client{i % len(ranks)}",
                          max_new_tokens=STEPS,
                          slo_class="interactive" if i % 2 == 0
                          else "batch") for i in range(8)]
    outs = engine.run()

    # spec engine on the SAME recorder: replay those answers as drafts
    drafter = ScriptedDrafter()
    spec = ServeEngine(params, cfg, registry, max_batch=4,
                       max_seq=PROMPT_LEN + STEPS, drafter=drafter,
                       spec_k=4, recorder=rec, metrics=metrics,
                       name="spec")
    for i in range(4):
        u = spec.submit(prompts[i], f"client{i % len(ranks)}",
                        max_new_tokens=STEPS)
        drafter.set(u, outs[uids[i]])
    spec.run()

    os.makedirs("results", exist_ok=True)
    doc = write_chrome_trace(rec.events(), "results/serve_trace.json",
                             dropped=rec.dropped)
    counts = validate_chrome_trace(doc)
    n = write_jsonl(rec.events(), "results/serve_events.jsonl")
    names = {e[1] for e in rec.events()}
    covered = [s for s in ("serve.prefill_chunk", "serve.decode_step",
                           "serve.draft", "serve.verify_step", "preempt",
                           "replay", "defer")
               if s in names]
    print(f"\nobservability: {n} events ({counts['X']} spans) on "
          f"{len({e[2] for e in rec.events()})} tracks -> "
          f"results/serve_trace.json (drop into ui.perfetto.dev)")
    print(f"  span/instant coverage: {', '.join(covered)}")
    print(f"  {engine.preemptions} preemptions, {engine.deferrals} "
          f"deferrals visible in-trace; spec acceptance "
          f"{spec.accepted_tokens / max(spec.drafted_tokens, 1):.2f}")

    # the watching layer over the same events: time series, SLOs over
    # the per-class TTFT, and the static ops report
    store = SeriesStore(bucket_s=0.25)
    store.fold(rec.events())
    slo = SLOMonitor([
        Objective("ttft", series="first_token.ttft_s", threshold=60.0,
                  target=0.9),
        Objective("decode", series="span.serve.decode_step", threshold=60.0,
                  target=0.9)], recorder=rec)
    slo.fold(rec.events())
    write_html("results/serve_report.html",
               title="serve_adapters ops report", store=store, slo=slo,
               metrics=metrics, dropped=rec.dropped)
    att = ", ".join(f"{c}={a:.0%}"
                    for c, a in engine.slo_attainment().items())
    print(f"  slo attainment: {att} -> results/serve_report.html")
    print(snapshot_text(store=store, slo=slo, title="  -- snapshot --"))
    print(metrics.summary_text("  -- metrics --"))


if __name__ == "__main__":
    main()
    oversubscribed()
    speculative()
    observability()
