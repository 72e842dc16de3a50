"""The trace reduction on hand-made events, and on a small trace
recorded on a TPU v5e (``record_trace.py``)."""
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "small.xplane.pb"


def test_reduce_events_by_hand():
    window = (0.0, 100.0)
    ops = [[("a", 10, 30), ("b", 20, 40), ("a", 60, 70), ("c", 95, 120)]]
    mods = [[("jit_decode_step(1)", 10, 40), ("jit_decode_step(1)", 60, 70),
             ("jit_other", 95, 120)]]
    spans = [("dispatch", 0, 12), ("token_read", 40, 58),
             ("dispatch", 45, 50)]
    s = trace.reduce_events(window, ops, mods, spans)
    assert s["window_s"] == pytest.approx(100e-9)
    # busy: [10, 40] + [60, 70] + [95, 100]
    assert s["busy_s"] == pytest.approx(45e-9)
    assert s["device_ops"][0] == ["a", pytest.approx(30e-9)]
    gaps = dict((n, t) for n, t in s["idle_gaps"])
    # gaps [0,10] under dispatch, [40,60] at 50 under token_read (the
    # dispatch span ends at 50), [70,95] under nothing
    assert gaps["dispatch"] == pytest.approx(10e-9)
    assert gaps["token_read"] == pytest.approx(20e-9)
    assert gaps["other"] == pytest.approx(25e-9)
    assert s["programs"]["jit_decode_step(1)"] == {
        "count": 2, "device_s": pytest.approx(40e-9)}


def test_busy_averages_over_devices():
    s = trace.reduce_events((0, 10), [[("x", 0, 10)], [("x", 0, 5)]],
                            [[], []], [])
    assert s["busy_s"] == pytest.approx(7.5e-9)


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_tpu_trace():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA))
    window, ops, mods, spans = trace.events_from_profile(
        pd, ("dispatch", "token_read"))
    s = trace.reduce_events(window, ops, mods, spans)
    assert 0 < s["busy_s"] < s["window_s"]
    # three executions of the one program, and idle time while the host
    # slept in token_read
    assert sum(p["count"] for p in s["programs"].values()) == 3
    assert "token_read" in {n for n, _ in s["idle_gaps"]}
