"""The instruction-tuning driver at reduced widths on the CPU: a window,
a well-formed line, and the faults and control the check must catch."""
import pytest

import bench
import cells
import hybrid_cells
from drivers import fed_lm


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return hybrid_cells.lm_cell(tmp_path_factory.mktemp("lm"))


def test_lm_window_line_is_well_formed(cell):
    line = cells.run_line(cell, seconds=1.0)
    cells.assert_well_formed(line, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["round_s.sync"]["value"] > 0
    assert line["routing"]["moe_dropped"] == 0
    assert line["routing"]["moe_routed"] > 0


def test_lm_traced_window(cell):
    line = cells.run_line(cell, seconds=1.0, trace=True)
    cells.assert_well_formed(line, cell, trace=True)
    assert "fed.wire_bytes.instruct" in line["metrics"]
    assert line["device"]["window_s"] > 0


@pytest.mark.parametrize("mode", sorted(fed_lm.FAULTS))
def test_lm_faults_come_out_not_correct(cell, mode):
    """Each planted fault, capacity-dropping routing among them, comes out
    not correct under the cell's limits. (The float8 control is judged on
    the chip, where the limits were set: at these widths its loss gap is
    smaller than at the published ones.)"""
    checks = bench.judge(fed_lm.check_readings(cell, 3, mode),
                         cell.limits["checks"])
    assert not all(c["ok"] for c in checks.values()), (mode, checks)


def test_lm_program_comes_out_correct(cell):
    r = fed_lm.check_readings(cell, 3, "program")
    checks = bench.judge(r, cell.limits["checks"])
    assert all(c["ok"] for c in checks.values()), checks
    assert r["moe_dropped"] == 0


def test_capacity_fault_caps_each_client_alone(monkeypatch):
    """The planted capacity routing caps an expert's pairs in each
    client's step, not over the cohort the vmapped layer folds into one
    grouped matmul: with every token sent to the same experts, each
    client keeps ``cap`` pairs of each and reports the rest as dropped,
    and its output and input gradient equal its own unbatched call."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_reduced
    from repro.models import moe

    cfg = get_reduced("granite-4.0-h-small")
    p = jax.tree.map(lambda a: a[0], moe.init_routed_params(
        jax.random.PRNGKey(0), cfg, 1, jnp.float32))
    p = dict(p, router=jnp.zeros_like(p["router"]))
    k, clients, s = cfg.experts_per_token, 3, 16
    cap = 8
    monkeypatch.setattr(moe, "_held_pairs",
                        fed_lm.capacity_pairs(moe._held_pairs, s, cap))
    x = jax.random.normal(jax.random.PRNGKey(1), (clients, 1, s, cfg.d_model))

    def f(x):
        return moe.routed_moe(x, p, cfg)

    def g(x):
        return jax.grad(lambda x: jnp.sum(f(x)[0] ** 2))(x)
    y, st = jax.vmap(f)(x)
    np.testing.assert_array_equal(st["dropped"], (s - cap) * k)
    np.testing.assert_array_equal(st["load"][:, :k], s)
    gv = jax.vmap(g)(x)
    for c in range(clients):
        np.testing.assert_allclose(y[c], f(x[c])[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(gv[c], g(x[c]), rtol=1e-5, atol=1e-5)


def test_capacity_fault_drops_after_the_program_ran(cell):
    """The capacity fault traces a trainer of its own, so it drops pairs
    even where the program's trainer was compiled first in the process;
    the program drops none."""
    prog = fed_lm.check_readings(cell, 4, "program")
    capped = fed_lm.check_readings(cell, 4, "capacity")
    assert prog["moe_dropped"] == 0
    assert capped["moe_dropped"] > 0
