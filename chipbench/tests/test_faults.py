"""A whole run of the training cell at the smoke sizes on the CPU, the
chip check skipped: sound, it comes out correct under the cell's limits;
with the timed path broken underneath, or with the control (the
reference in a lower precision) in the program's place, it does not."""

import pytest

from chipbench import harness as H

SEED = 2 ** 33 + 11
TRAIN = "mamba2-130m.train.carousel"


@pytest.fixture
def cell(monkeypatch):
    """The cell at the smoke sizes, with the program's registry and the
    peak table steered to them."""
    import repro.configs.base as B
    import repro.launch.train as T
    monkeypatch.setattr(H, "peak", lambda kind: {"bf16_flops": 1e12})

    def make(name):
        get = B.get_smoke_config
        monkeypatch.setattr(B, "get_config", get)
        monkeypatch.setattr(T, "get_config", get)
        c = H.resolve(name)
        cfg = get(c.config["program_arch"])
        m = {k: getattr(cfg, k, v) for k, v in c.config["model"].items()}
        c.config = dict(c.config, model=m)
        run = dict(c.config["train"]["run"], ce_block_v=64)
        c.config["train"] = dict(c.config["train"], run=run)
        c.traffic = dict(c.traffic, seq_len=256, global_batch=4,
                         mean_doc_len=128)
        return c
    return make


def run_cell(cell, seconds=1.0):
    r = H.Run(cell, SEED, seconds, False)
    H.driver(cell.traffic).run(r, H.CompileCounter())
    return r


def failed(r):
    return {c["name"] for c in r.checks if not c["ok"]}


def test_sound_run_is_correct(cell):
    r = run_cell(cell(TRAIN))
    assert r.correct, r.checks
    assert r.attempted > 0 and r.failed == 0


def _patch_train_step(monkeypatch, broken):
    import repro.launch.train as T
    real = T.make_train_step
    monkeypatch.setattr(T, "make_train_step",
                        lambda cfg, run: broken(real(cfg, run)))


def test_train_state_unchanged(cell, monkeypatch):
    _patch_train_step(monkeypatch, lambda step: (
        lambda state, batch: (state, step(state, batch)[1])))
    assert "change_gap" in failed(run_cell(cell(TRAIN)))


def test_train_half_batch(cell, monkeypatch):
    _patch_train_step(monkeypatch, lambda step: (
        lambda state, batch: step(state, {k: v[: v.shape[0] // 2]
                                          for k, v in batch.items()})))
    assert failed(run_cell(cell(TRAIN)))


def test_train_token_altered(cell, monkeypatch):
    import repro.launch.train as T
    real = T.make_packing_transform

    def altered(seq_len, **kw):
        tf = real(seq_len, **kw)

        def _tf(name, raw):
            out = tf(name, raw)
            out["tokens"][0, 5] += 1
            return out
        return _tf
    monkeypatch.setattr(T, "make_packing_transform", altered)
    assert "rows_not_staged" in failed(run_cell(cell(TRAIN)))


def test_train_control_is_not_correct(cell):
    """The reference with float8 products in the program's place, on the
    batches a sound run consumed: some number passes its limit."""
    from chipbench.control import LOWER
    from chipbench.drivers import train_carousel as D
    from chipbench.reference import mamba2
    from chipbench.reference.numerics import Numerics
    c = cell(TRAIN)
    r = run_cell(c)
    m = c.config["model"]
    control = D.reference_readings(
        mamba2.param_spec(m), m, c.config, SEED, r.compared["batches"],
        Numerics(LOWER[m["param_dtype"]]))
    g = D.gaps(control, r.compared["reference"])
    assert any(g[k] > c.limits[k] for k in g if k in c.limits), g
