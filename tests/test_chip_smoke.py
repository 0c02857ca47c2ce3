"""``chip_smoke.py`` kept honest on the CPU.

The script's phase functions run here at the reduced widths of the same
two models (``main()`` alone picks the published widths and requires the
TPU), so a change that breaks the bring-up path fails tier-1 before it
costs chip time. The four-chip phases run in the mesh child of
``tests/test_mesh.py``.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np

import chip_smoke
from repro.configs import get_reduced
from repro.fed.simulation import SimConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_fed_phase_reduced():
    sim = SimConfig(task="mrpc", num_examples=256, eval_examples=64,
                    local_steps=2, local_batch=8, pretrain_steps=0)
    facts = chip_smoke.fed_phase(get_reduced("roberta-large"), sim)
    assert len(facts["train_loss"]) == 2
    assert np.all(np.isfinite(facts["train_loss"]))
    assert min(facts["downlink_bytes"]) > 0
    assert min(facts["uplink_bytes"]) > 0
    assert facts["recon_agg_rel_frob"] <= chip_smoke.AGG_REL_TOL


def test_serve_phase_reduced():
    facts = chip_smoke.serve_phase(get_reduced("gemma-2b"), prompt_len=16,
                                   new_tokens=6)
    assert facts["first_token_agree"] == "8/8"
    assert facts["trace_count"] == 2            # prefill + decode, replayed


def test_main_refuses_cpu_and_prints_no_result():
    proc = subprocess.run([sys.executable, SCRIPT], env=_cpu_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert "{" not in proc.stdout


def test_script_alone_fails(tmp_path):
    """Without the rest of the repository the script cannot run."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = _cpu_env()
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_imports_stay_off_the_forced_device_launchers():
    """The chip path imports the serving and federated library code and
    nothing host-side: no benchmark runner and no CLI driver."""
    code = ("import json, sys; import chip_smoke; print(json.dumps("
            "sorted(m for m in sys.modules if m.split('.')[0] in "
            "('repro', 'benchmarks', 'perfbench'))))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=_cpu_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    mods = json.loads(proc.stdout.splitlines()[-1])
    assert "repro.serve" in mods and "repro.fed.session" in mods
    assert "repro.launch.train" not in mods
    assert not [m for m in mods if not m.startswith("repro")], mods
