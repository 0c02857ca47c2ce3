"""The federated driver at reduced widths on the CPU: a window, a
well-formed line, and the faults and control the check must catch."""
import pytest

import bench
import cells
from drivers import fed


def test_fed_window_line_is_well_formed(tmp_path):
    cell = cells.fed_cell(tmp_path)
    line = cells.run_line(cell)
    cells.assert_well_formed(line, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["round_s.sync"]["value"] > 0


def test_fed_traced_window(tmp_path):
    cell = cells.fed_cell(tmp_path, traffic="xdevice", like="fed_xdevice")
    line = cells.run_line(cell, trace=True)
    cells.assert_well_formed(line, cell, trace=True)
    assert "fed.wire_bytes.xdevice" in line["metrics"]
    assert line["device"]["window_s"] > 0
    assert "idle_gaps" in line["breakdown"]


def test_fed_faults_come_out_not_correct(tmp_path):
    cell = cells.fed_cell(tmp_path)
    for name, fault in fed.FAULTS.items():
        line = cells.run_line(cell, seconds=0.5, wrap_train=fault)
        assert line["correct"] is False, name


@pytest.mark.parametrize("traffic", ["sync_mrpc", "xdevice"])
def test_fed_aggregation_left_unchanged_comes_out_not_correct(
        tmp_path, monkeypatch, traffic):
    """The server's merge returns the global state unchanged: the
    cell's aggregation check reads it."""
    from repro.fed.session import FedSession
    merge = FedSession.aggregate_round

    def unchanged(self, *a, **kw):
        lora, head = self.global_lora, self.global_head
        merge(self, *a, **kw)
        self.global_lora, self.global_head = lora, head
    monkeypatch.setattr(FedSession, "aggregate_round", unchanged)
    cell = cells.fed_cell(tmp_path, traffic=traffic, like="fed_" + traffic)
    line = cells.run_line(cell, seconds=0.5)
    assert line["correct"] is False
    agg = [v["value"] for k, v in line["checks"].items()
           if k.startswith("aggregate_")]
    assert agg and agg[0] > 0.9


def test_fed_one_leaf_left_unmoved_comes_out_not_correct(tmp_path):
    """One layer's q factor A keeps its start on every client: the
    per-leaf change, averaged over the clients, reads it."""
    def one_leaf(trainer):
        def train(frozen, trainable, masks, data):
            out, losses = trainer(frozen, trainable, masks, data)
            a = out["factors"]["q"]["A"].at[:, 1].set(
                trainable["factors"]["q"]["A"][:, 1])
            fac = dict(out["factors"], q=dict(out["factors"]["q"], A=a))
            return dict(out, factors=fac), losses
        return train
    cell = cells.fed_cell(tmp_path)
    line = cells.run_line(cell, seconds=0.5, wrap_train=one_leaf)
    c = line["checks"]["client_change_leaf"]
    assert line["correct"] is False and c["value"] > c["limit"]


def test_fed_control_separates_from_the_program(tmp_path):
    """The control (the reference with every product in float8 in the
    program's place) comes out not correct under the cell's limits while
    the program comes out correct; the chip readings that set the limits
    are in PERF.md."""
    cell = cells.fed_cell(tmp_path)
    limits = cell.limits["checks"]
    prog = bench.judge(fed.check_readings(cell, 3, "program"), limits)
    ctrl = bench.judge(fed.check_readings(cell, 3, "control"), limits)
    assert all(c["ok"] for c in prog.values()), prog
    assert not all(c["ok"] for c in ctrl.values()), ctrl
