"""Paper-table runner: reproduces the HLoRA paper's accuracy and byte
experiments on the host. It measures no speed; speed is measured on the
chip by ``perfbench/``.

  convergence   bench_convergence — Fig. 3 curves + Table 1 accuracies
  bias          bench_bias        — Eq. 1 aggregation bias, measured
  comm          bench_comm        — per-round communication volume (C4)

Output: CSV lines ``name,derived`` + the Table 1 markdown, merged into
results/bench_results.json.

Merge semantics: each section runs isolated — one crashing section
cannot take down the others, and a section that *failed* this
invocation keeps its previous good numbers in the json instead of
clobbering them (its error lands under ``"_errors"``). Sections not
re-run this invocation keep their previous numbers. The json write is
atomic (tmp + rename), so an interrupt never leaves a half-written file.

``--quick`` is a smoke mode: every section at tiny shapes (tier-1 runs
it, so the scripts cannot silently rot). Its numbers are pipeline
checks, not the paper's magnitudes, so it defaults to a separate
``results/bench_quick.json`` instead of the canonical file.

  PYTHONPATH=src python -m benchmarks.run [--only bias,comm] [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import bench_bias, bench_comm, bench_convergence

ALL = ("convergence", "bias", "comm")


def _run_convergence(args):
    conv = bench_convergence.run(quick=args.quick)
    print("\n## Table 1 reproduction (accuracy %, mean over seeds)\n")
    print(bench_convergence.table1(conv))
    return conv


def _runners(args):
    # declaration order == execution order (cheap sections first)
    return {
        "comm": lambda: bench_comm.run(quick=args.quick),
        "bias": lambda: bench_bias.run(quick=args.quick),
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
    ap.add_argument("--out", default="results/bench_results.json")
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
    results, errors = {}, {}

    print("name,derived")
    for name, runner in _runners(args).items():
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
    print(f"\n[benchmarks] {len(results)}/{len(results) + len(errors)} "
          f"sections ok -> {args.out}")
    return 1 if errors else 0


if __name__ == "__main__":
    # the CLI entry only: tests call main() in-process and must not
    # write a compilation cache
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
