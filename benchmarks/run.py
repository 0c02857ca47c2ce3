"""Benchmark harness — one module per paper table/figure + system benches.

  convergence   bench_convergence — Fig. 3 curves + Table 1 accuracies
  bias          bench_bias        — Eq. 1 aggregation bias, measured
  server        bench_server      — aggregation strategy cost
  comm          bench_comm        — per-round communication volume (C4)
  svd           bench_svd         — SVD back-end scaling
  serve         bench_serve       — multi-LoRA serving throughput + paged KV
  roofline      bench_roofline    — 3-term roofline from the dry-run
  fed           bench_fed         — FedSession schedulers + measured wire bytes
  obs           bench_obs         — shared-recorder trace capture + export checks

The ``fed`` and ``serve`` sections each end with a mesh-scaling
subsection (``mesh_*`` keys): the shard_map'd engine at 1 vs N forced
host devices, measured in a subprocess child (the device count must be
forced before jax initializes) with single-device equivalence asserted.
The child always runs on the CPU (``JAX_PLATFORMS=cpu``), so the
``mesh_*`` keys are CPU numbers on whatever host runs the harness, never
accelerator figures.

Output: CSV lines ``name,us_per_call,derived`` + markdown tables,
merged into results/bench_results.json.

Merge semantics (hardened): each section runs isolated — one crashing
section cannot take down the others, and a section that *failed* this
invocation keeps its previous good numbers in the json instead of
clobbering them (its error lands under ``"_errors"``). Sections not
re-run this invocation keep their previous numbers. The json write is
atomic (tmp + rename), so an interrupt never leaves a half-written file.

``--quick`` is a smoke mode: every section at tiny shapes in ~1-2 min
total (tier-1 runs it, so benchmark scripts cannot silently rot). Its
numbers are pipeline checks, not magnitudes, so it defaults to a
separate ``results/bench_quick.json`` instead of the canonical file.

Every invocation also appends its flattened numeric results to a
history JSONL beside --out (``results/bench_history.jsonl`` for the
canonical file); ``--check`` turns that history into a perf-regression
gate — rc=2 when a curated throughput/latency key moved past the
threshold in the bad direction vs the previous run in the same mode
(20% at full scale, 50% under --quick whose tiny shapes jitter ~±30%).

  PYTHONPATH=src python -m benchmarks.run [--only svd,comm] [--quick]
  PYTHONPATH=src python -m benchmarks.run --quick --check
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import (bench_bias, bench_comm, bench_convergence,
                        bench_fed, bench_obs, bench_roofline, bench_serve,
                        bench_server, bench_svd)

ALL = ("convergence", "bias", "server", "comm", "svd", "serve", "roofline",
       "fed", "obs", "analysis")

# -- perf-regression gate ----------------------------------------------------
#
# Every invocation appends its flattened numeric results to a history
# JSONL next to --out; ``--check`` compares the curated keys below
# against the previous run with the same --quick flag and fails the
# process (rc=2) on a move in the bad direction past the threshold.
# The threshold is mode-aware: full-scale runs are long enough that 20%
# is comfortably above machine noise, but --quick smoke shapes (2 fed
# rounds, 4 serve requests) carry ~±30% wall-clock jitter even on an
# idle box, so quick mode gates at 50% — still far below the 2-10x
# moves a real perf rot produces. The allowlist is deliberately small:
# throughput/latency keys plus the deterministic wire-byte counters.
# Deliberately EXCLUDED: ``mesh_*`` keys (forced host-device subprocess
# timings are scheduler artifacts, e.g. mesh_tok_per_s_sharded swings 2x
# run to run) and pure correctness keys (asserted inside the sections,
# a gate adds nothing).

REGRESSION_THRESHOLD = 0.20
QUICK_REGRESSION_THRESHOLD = 0.50

REGRESSION_KEYS = {
    # section.key                       higher is better?
    "serve.engine_tok_per_s": True,
    "serve.merged_tok_per_s": True,
    "serve.prefill_chunked_tok_per_s": True,
    "serve.spec_forced_tok_per_s": True,
    "serve.obs_ttft_p99_ms": False,
    "fed.obs_round_ms_p99": False,
    "server.tree_engine": False,           # us/call
    # measured wire bytes/round: deterministic (serialized buffer lengths,
    # not timings), so any drift is a real format/accounting change
    "fed.obs_downlink_bytes_per_round": False,
    "fed.obs_uplink_bytes_per_round": False,
    "fed.hier_edge_uplink_bytes_per_round": False,
}


def flatten_numeric(results: dict) -> dict:
    """``{"section.key": float}`` over finite numeric leaves; private
    ``_``-prefixed keys (and non-numeric values) are skipped."""
    flat = {}
    for section, vals in results.items():
        if section.startswith("_") or not isinstance(vals, dict):
            continue
        for k, v in vals.items():
            # sections like convergence key sub-dicts by int rank —
            # only flat string-keyed numeric leaves are history-worthy
            if not isinstance(k, str) or k.startswith("_") \
                    or isinstance(v, bool):
                continue
            if isinstance(v, (int, float)) and v == v \
                    and v not in (float("inf"), float("-inf")):
                flat[f"{section}.{k}"] = float(v)
    return flat


def append_history(path: str, flat: dict, quick: bool) -> dict | None:
    """Append one ``{"ts", "quick", "results"}`` line (atomic: the
    rewritten file is swapped in with os.replace) and return the most
    recent PRIOR entry with the same quick flag, or None."""
    entries = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entries.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # torn line from a crashed writer: drop it
    prev = None
    for e in reversed(entries):
        if bool(e.get("quick")) == bool(quick):
            prev = e
            break
    entries.append({"ts": time.time(), "quick": bool(quick),
                    "results": flat})
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        for e in entries:
            f.write(json.dumps(e, default=float) + "\n")
    os.replace(tmp, path)
    return prev


def check_regressions(prev_flat: dict, cur_flat: dict,
                      keys=None, threshold: float = REGRESSION_THRESHOLD
                      ) -> list:
    """Curated keys present in BOTH runs that moved more than
    ``threshold`` in the bad direction. Returns ``[(key, prev, cur,
    rel_change), ...]`` — empty means the gate passes."""
    bad = []
    for key, higher_better in (keys or REGRESSION_KEYS).items():
        if key not in prev_flat or key not in cur_flat:
            continue
        prev, cur = prev_flat[key], cur_flat[key]
        if prev <= 0:
            continue
        rel = (cur - prev) / prev
        regressed = rel < -threshold if higher_better \
            else rel > threshold
        if regressed:
            bad.append((key, prev, cur, rel))
    return bad


def history_path_for(out_path: str) -> str:
    """``results/bench_results.json -> results/bench_history.jsonl``;
    any other --out gets ``<stem>_history.jsonl`` beside it."""
    d = os.path.dirname(out_path)
    stem = os.path.splitext(os.path.basename(out_path))[0]
    if stem == "bench_results":
        return os.path.join(d or ".", "bench_history.jsonl")
    return os.path.join(d or ".", f"{stem}_history.jsonl")


def _run_roofline(args):
    rows = bench_roofline.run(args.dryrun_jsonl, quick=args.quick)
    print("\n## Roofline (single-pod 16x16)\n")
    print(bench_roofline.markdown_table(rows, "16x16"))
    print("\n## Collective bytes: paper-faithful baseline vs optimized"
          " (§Perf)\n")
    print(bench_roofline.compare())
    return rows


def _run_convergence(args):
    conv = bench_convergence.run(quick=args.quick)
    print("\n## Table 1 reproduction (accuracy %, mean over seeds)\n")
    print(bench_convergence.table1(conv))
    return conv


def _run_analysis(args):
    """Invariant lint suite smoke: the CLI must list a healthy pass
    registry (>=5 rules) and the shipped tree must lint clean — through
    the real ``python -m repro.analysis`` entry point in a subprocess,
    so a broken registry import or CLI regression fails the tier-1
    smoke run instead of silently rotting."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env = dict(os.environ, PYTHONPATH=src + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    t0 = time.time()
    ls = subprocess.run([sys.executable, "-m", "repro.analysis", "--list"],
                        capture_output=True, text=True, env=env)
    rules = [l for l in ls.stdout.splitlines() if " — " in l]
    tree = subprocess.run([sys.executable, "-m", "repro.analysis",
                           os.path.join(src, "repro")],
                          capture_output=True, text=True, env=env)
    if tree.returncode != 0:
        print(tree.stdout)
    res = {"rules_listed": len(rules),
           "cli_list_rc": ls.returncode,
           "tree_rc": tree.returncode,
           "tree_clean": 1 if tree.returncode == 0 else 0,
           "lint_s": round(time.time() - t0, 2)}
    print(f"analysis,lint_full_tree,{res['rules_listed']} rules "
          f"tree_clean={res['tree_clean']}")
    return res


def _runners(args):
    # declaration order == execution order (cheap sections first)
    return {
        "analysis": lambda: _run_analysis(args),
        "comm": lambda: bench_comm.run(quick=args.quick),
        "obs": lambda: bench_obs.run(quick=args.quick),
        "svd": lambda: bench_svd.run(quick=args.quick),
        "server": lambda: bench_server.run(quick=args.quick),
        "fed": lambda: bench_fed.run(quick=args.quick),
        "serve": lambda: bench_serve.run(quick=args.quick),
        "bias": lambda: bench_bias.run(quick=args.quick),
        "roofline": lambda: _run_roofline(args),
        "convergence": lambda: _run_convergence(args),
    }


def merge_results(path: str, results: dict, errors: dict) -> dict:
    """Previous json + this run's sections; failed sections keep their
    old numbers and record the failure under '_errors'. Atomic write."""
    merged = {}
    if os.path.exists(path):  # keep sections not re-run this time
        try:
            with open(path) as f:
                merged = json.load(f)
        except (json.JSONDecodeError, OSError):
            pass  # corrupt/partial previous file: overwrite, don't crash
    prev_errors = merged.pop("_errors", {})
    merged.update(results)
    # a section that succeeded now clears its stale error; a section that
    # failed now records one *without* touching its previous numbers
    for name in results:
        prev_errors.pop(name, None)
    prev_errors.update(errors)
    if prev_errors:
        merged["_errors"] = prev_errors
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    os.replace(tmp, path)
    return merged


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help=f"comma-separated subset of {','.join(ALL)}")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--dryrun-jsonl", default="results/dryrun.jsonl")
    ap.add_argument("--out", default="results/bench_results.json")
    ap.add_argument("--history", default=None,
                    help="history JSONL path (default: derived from "
                         "--out, e.g. results/bench_history.jsonl)")
    ap.add_argument("--check", action="store_true",
                    help="fail (rc=2) on a >20%% regression vs the "
                         "previous same-mode run on the curated keys")
    args = ap.parse_args(argv)
    if args.quick and args.out == ap.get_default("out"):
        # quick is a smoke mode (tiny shapes, meaningless magnitudes):
        # never let it silently merge over the canonical numbers. An
        # explicit --out still wins.
        args.out = "results/bench_quick.json"
        print(f"[benchmarks] --quick: writing {args.out} (pass --out to "
              f"override; the canonical json is full-run only)")
    if args.only == "all":
        which = ALL
    else:
        which = tuple(s for s in args.only.split(",") if s)
        unknown = sorted(set(which) - set(ALL))
        if unknown:
            ap.error(f"unknown section(s) {unknown}; valid: {list(ALL)}")
    runners = _runners(args)
    results, errors = {}, {}
    t0 = time.time()

    print("name,us_per_call,derived")
    for name, runner in runners.items():
        if name not in which:
            continue
        try:
            results[name] = runner()
        except Exception as e:  # noqa: BLE001 — isolate section failures
            traceback.print_exc()
            errors[name] = f"{type(e).__name__}: {e}"
            print(f"[benchmarks] section {name!r} FAILED — previous "
                  f"numbers (if any) are kept")

    merge_results(args.out, results, errors)
    status = f"{len(results)}/{len(results) + len(errors)} sections ok"
    print(f"\n[benchmarks] {status} in {time.time() - t0:.1f}s "
          f"-> {args.out}")

    # perf history + optional regression gate (only sections actually
    # run this invocation land in the history line)
    hist_path = args.history or history_path_for(args.out)
    flat = flatten_numeric(results)
    prev = append_history(hist_path, flat, args.quick)
    print(f"[benchmarks] history +1 entry -> {hist_path}")
    if args.check:
        if prev is None:
            print("[benchmarks] --check: no previous same-mode run in "
                  "history; gate passes vacuously")
        else:
            threshold = (QUICK_REGRESSION_THRESHOLD if args.quick
                         else REGRESSION_THRESHOLD)
            regressions = check_regressions(prev["results"], flat,
                                            threshold=threshold)
            for key, pv, cv, rel in regressions:
                print(f"[benchmarks] REGRESSION {key}: {pv:.4g} -> "
                      f"{cv:.4g} ({rel:+.1%}, threshold "
                      f"{threshold:.0%})")
            if regressions:
                print(f"[benchmarks] --check FAILED: "
                      f"{len(regressions)} regressed key(s)")
                return 2
            checked = sum(1 for k in REGRESSION_KEYS
                          if k in prev["results"] and k in flat)
            print(f"[benchmarks] --check ok ({checked} curated keys "
                  f"within {threshold:.0%})")
    return 1 if errors else 0


if __name__ == "__main__":
    # the CLI entry only: tests call main() in-process and must not
    # write a compilation cache
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
