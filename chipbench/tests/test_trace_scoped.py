"""The sharded driver's tracer: the trace reduced by scope and by
collective over every chip before it is deleted, on hand-made events and
on the scoped trace recorded on a TPU v5e (``record_scoped_trace.py``)."""
import shutil
from pathlib import Path

import pytest

from chipbench import harness as H
from chipbench import trace_scoped

DATA = Path(__file__).parent / "data" / "scoped.xplane.pb"


def test_collectives_by_hand():
    window = (0.0, 100.0)
    ops = [[("all-gather-start.1 bf16[8]", 0, 10),
            ("all-gather-done.1 bf16[8]", 10, 20),
            ("fusion.3 f32[8]", 20, 40),
            ("reduce-scatter.2 f32[2]", 90, 110),
            ("collective-permute-start.4 bf16[8]", 40, 50)],
           [("all-reduce.5 f32[]", 0, 30)]]
    total, top = trace_scoped.collectives(window, ops)
    # device 0: 10 + 10 + 10 (clipped) + 10; device 1: 30; averaged
    assert total == pytest.approx((40 + 30) / 2 / 1e9)
    assert top[0] == ("all-reduce.5 f32[]", pytest.approx(15e-9))
    assert "fusion.3 f32[8]" not in dict(top)


def test_within_counts_the_scopes_nested_in_it():
    """An op anywhere inside ``shared_block`` counts for it, whatever scope
    is innermost; an op outside it, or outside the window, does not."""
    window = (0.0, 100.0)
    tf_op = {"fusion.1": "jit(step)/shared_block/norm/mul",
             "fusion.2": ("jit(step)/transpose(jvp(shared_block))/"
                          "attention/dot_general"),
             "fusion.3": "jit(step)/ssd/mul"}
    ops = [[("fusion.1", 0, 10), ("fusion.2", 10, 30),
            ("fusion.3", 30, 60), ("fusion.1", 95, 120)],
           [("fusion.1", 0, 20)]]
    got = trace_scoped.within(window, ops, tf_op, ["shared_block"])
    # device 0: 10 + 20 + 5 (clipped); device 1: 20; averaged
    assert got == {"shared_block": pytest.approx((35 + 20) / 2 / 1e9)}
    assert trace_scoped.within(window, ops, tf_op, ["adapter"]) == {}


def test_shared_block_reader():
    cell = H.Cell({"name": "t", "chips": 4}, {}, {}, {}, [], [])
    r = H.Run(cell, 1, 1.0, True)
    read = H.metric_reader("train.shared_block_ms")
    r.counters["steps"] = 10
    assert read(r) is None
    r.trace_summary = {"within": {}, "scopes": {"norm": {"fwd": 1.0}}}
    assert read(r) is None
    r.trace_summary["within"]["shared_block"] = 0.5
    assert read(r) == pytest.approx(50.0)


def test_stop_reduces_scopes_and_collectives(tmp_path, monkeypatch):
    """On the recorded trace: the scoped layer's time under ``proj``, no
    collective, nothing inside ``shared_block`` (a mamba2 step has no
    such block), and the trace directory removed."""
    import jax
    cell = H.Cell({"name": "t", "chips": 1}, {}, {}, {}, [], [])
    r = H.Run(cell, 1, 1.0, True)
    tracer = trace_scoped.ScopedTracer(r)
    tracer.dir = tmp_path / "trace"
    prof = tracer.dir / "plugins" / "profile" / "1"
    prof.mkdir(parents=True)
    shutil.copy(DATA, prof / "x.xplane.pb")
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace_scoped.scopes, "program_scopes",
                        lambda: ("proj", "shared_block"))
    tracer.stop(r, ())
    t = r.trace_summary
    assert sum(t["scopes"]["proj"].values()) > 0
    assert t["collectives_s"] == 0 and t["collective_ops"] == []
    assert t["within"] == {}
    assert 0 < t["busy_s"] < t["window_s"]
    assert not tracer.dir.exists()
