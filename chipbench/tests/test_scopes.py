"""Device time per named scope: the reduction on hand-made events, and
the ``tf_op`` reader on traces recorded on a TPU v5e
(``record_trace.py``, ``record_scoped_trace.py``)."""
from pathlib import Path

import pytest

from chipbench import scopes
from chipbench import trace

DATA = Path(__file__).parent / "data"
SCOPES = ("embed", "norm", "proj", "ssd", "logits_ce", "adamw")


@pytest.mark.parametrize("tf_op,scope,part", [
    ("jit(step)/jvp()/while/body/closed_call/ssd/tanh", "ssd", "fwd"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/ssd/mul",
     "ssd", "bwd"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/proj/dot_general", "proj", "recompute"),
    ("jit(step)/jvp(logits_ce)/norm/cos", "norm", "fwd"),
    ("jit(step)/transpose(jvp(logits_ce))/while/body/add", "logits_ce",
     "bwd"),
    ("jit(step)/transpose(jvp(embed))/jit(_take)/scatter-add", "embed",
     "bwd"),
    ("jit(step)/jvp()/while/body/closed_call/jit(norm)/sqrt", None, "fwd"),
    ("jit(<lambda>)/dot_general:", None, "fwd"),
    ("jit(step)/adamw/sqrt:", "adamw", "fwd"),
    ("", None, "fwd"),
])
def test_scope_and_part_of_a_path(tf_op, scope, part):
    assert scopes.scope_of(tf_op, SCOPES) == scope
    assert scopes.part_of(tf_op) == part


def test_reduce_scopes_by_hand():
    window = (0.0, 100.0)
    ops = [[("while.1", 0, 45), ("a", 0, 10), ("b", 10, 30), ("c", 30, 45),
            ("d", 50, 60), ("e", 60, 70), ("f", 95, 120)]]
    tf_op = {
        "a": "jit(s)/jvp(embed)/gather",
        "b": "jit(s)/jvp()/while/body/closed_call/proj/norm/dot_general",
        "c": "jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/"
             "proj/dot_general",
        "d": "jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/"
             "rematted_computation/proj/dot_general",
        "e": "jit(s)/transpose(jvp())/while/body/dynamic_slice",
        "f": "jit(s)/adamw/mul",
    }
    out = scopes.reduce_scopes(window, ops, tf_op, SCOPES)
    ns = pytest.approx
    assert out["embed"] == {"fwd": ns(10e-9), "bwd": 0.0, "recompute": 0.0}
    # nested: the innermost scope takes the time
    assert out["norm"] == {"fwd": ns(20e-9), "bwd": 0.0, "recompute": 0.0}
    assert out["proj"] == {"fwd": 0.0, "bwd": ns(15e-9),
                           "recompute": ns(10e-9)}
    assert out["unscoped"] == {"fwd": 0.0, "bwd": ns(10e-9),
                               "recompute": 0.0}
    assert out["adamw"]["fwd"] == ns(5e-9)           # clipped to the window
    # the loop's own event is left out; scopes and unscoped make up busy
    total = sum(sum(p.values()) for p in out.values())
    busy = trace.busy_ns(trace.clip(ops[0], *window)) / 1e9
    assert total == ns(busy)


def test_idle_by_label_sums_every_gap():
    ops = [("a", 10, 20), ("b", 40, 50)]
    spans = [("train.loss_read", 15, 30), ("train.next_batch", 30, 45),
             ("input", 32, 44)]
    out = scopes.idle_by_label((0, 60), ops, spans)
    # [0,10] and [50,60] under nothing; the gap [20,40] is cut where the
    # spans open and close: [20,30] loss_read, [30,32] next_batch, [32,40]
    # input, the innermost
    assert out == {"other": pytest.approx(20e-9),
                   "train.loss_read": pytest.approx(10e-9),
                   "train.next_batch": pytest.approx(2e-9),
                   "input": pytest.approx(8e-9)}


def test_tf_op_of_recorded_trace():
    ops = scopes.tf_ops(str(DATA / "small.xplane.pb"))
    fusion = [k for k in ops if k.startswith("convolution_tanh_fusion ")]
    assert len(fusion) == 1
    assert ops[fusion[0]] == "jit(<lambda>)/dot_general:"


def test_recorded_scoped_trace():
    """On the chip, the scoped layer's ops fall under ``proj`` in the
    forward, backward and recomputed passes."""
    out = scopes.reduce_file(str(DATA / "scoped.xplane.pb"), (),
                             scopes=("proj",))
    proj = out["scopes"]["proj"]
    assert all(proj[p] > 0 for p in scopes.PARTS), proj
    total = sum(sum(p.values()) for p in out["scopes"].values())
    assert total == pytest.approx(out["busy_s"], rel=0.01)
