"""Scope attribution (``scopes.py``) on a synthetic capture and module
text: each operation is looked up by its instruction name, nested events
count once, and operations outside the module do not count."""
import pytest

import scopes
import xtrace

DEV = "/device:TPU:0"
HLO = """
HloModule jit_local_train
  %fusion.1 = f32[8] fusion(%p), kind=kLoop, metadata={op_name="jit(local_train)/moe.experts/mul"}
  %while.2 = (s32[]) while(%t), body=%body, metadata={op_name="jit(local_train)/ssm.ssd/while"}
  ROOT %fusion.3 = f32[8] fusion(%q), kind=kLoop, metadata={op_name="jit(local_train)/transpose(jvp(ssm.ssd))/dot_general"}
  %ragged-dot-none.4 = bf16[8] custom-call(%a), metadata={op_name="jit(local_train)/moe.experts/ragged_dot"}
  %fusion.5 = f32[8] fusion(%r), metadata={op_name="jit(local_train)/ssm.ssd_other/x"}
"""


def _ev(name, s, d, line=xtrace.OPS_LINE):
    return (DEV, line, name, float(s), float(d), "")


def test_op_names_from_module_text():
    names = scopes.op_names(HLO)
    assert names["fusion.3"].endswith("dot_general")
    assert names["ragged-dot-none.4"].endswith("ragged_dot")


def test_scope_seconds():
    ev = [_ev("jit_local_train", 0, 1000, xtrace.MODULES_LINE),
          _ev("%fusion.1 = f32[8] fusion(...)", 10, 100),
          _ev("%while.2 = (s32[]) while(...)", 200, 300),
          _ev("%fusion.3 = f32[8] fusion(...)", 250, 100),   # in the loop
          _ev("%fusion.3 = f32[8] fusion(...)", 600, 50),
          _ev("%ragged-dot-none.4 = bf16[8] custom-call(...)", 700, 40),
          _ev("%fusion.5 = f32[8] fusion(...)", 800, 10),
          _ev("%fusion.1 = f32[8] fusion(...)", 2000, 100)]   # outside
    tr = xtrace.Trace(ev, window=(0.0, 3000.0))
    got = scopes.scope_seconds(tr, HLO, "jit_local_train",
                               ("moe.experts", "ssm.ssd"),
                               ops=("ragged-dot-none",))
    assert got["moe.experts"] == pytest.approx(140e-9)
    assert got["ssm.ssd"] == pytest.approx(350e-9)
    assert got["ragged-dot-none"] == pytest.approx(40e-9)
