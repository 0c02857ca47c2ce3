"""Importing the harness loads no JAX (so no topology and no TPU
library), which keeps the tests safe under several workers."""
import os
import subprocess
import sys

from fixtures import BENCH, ROOT


def test_harness_import_touches_no_jax():
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "import bench, xtrace, flops, readers, run, control\n"
        "from drivers import fed, serve\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "print('clean')\n" % (BENCH, os.path.join(ROOT, "src")))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout
