"""The entry point: no TPU, no result; a checkout holding only the
benchmark's files, no result; a cell, a configuration and a per-layer
metric added as new files only."""
import json
import os
import shutil
import subprocess
import sys

import bench
import cells
import fixtures


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "fed_sync_mrpc",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out):
    for line in out.stdout.strip().splitlines()[-1:]:
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(fixtures.ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    _no_result(out)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(fixtures.BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(fixtures.ROOT, "BENCHMARK.json"), tmp_path)
    out = _run(str(tmp_path))
    assert out.returncode != 0
    _no_result(out)


def test_new_cell_and_metric_from_new_files_only(tmp_path):
    """A configuration, a mix, limits and a per-layer metric reader, each
    a new file, plus one ``workloads`` and one ``per_layer`` entry."""
    cell = cells.fed_cell(tmp_path, traffic="xdevice", like="fed_xdevice")
    root = cell.root
    with open(os.path.join(root, "perfbench", "metrics",
                           "fed.rounds_traced.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx.counters.get('rounds_traced')\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    spec["per_layer"].append({
        "name": "fed.rounds_traced", "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "fed scheduler",
        "moves": "round_s.xdevice", "workloads": ["fed_tiny"]})
    json.dump(spec, open(spec_path, "w"))
    cell = bench.load_cell("fed_tiny", root=root)
    assert cell.config["name"] == "encoder-tiny"
    assert "fed.rounds_traced" in {m["name"] for m in cell.metrics_layer}
    line = cells.run_line(cell, trace=True)
    assert line["metrics"]["fed.rounds_traced"]["value"] >= 1
