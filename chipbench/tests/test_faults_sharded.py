"""Whole runs of ``zamba2-7b.train.fsdp4`` at the smoke sizes on the CPU,
the chip check skipped, on a mesh of every CPU device on the data axis
(four with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``): sound,
it is correct under the cell's limits, with the gradient's and the
change's set for the smoke widths; with the hybrid block broken in
the program (the original embeddings dropped from its input, the adapters
left out, one shared block used for both hybrid layers), or with the
reference in the program's place with the float8 control or the
exchange of gradients between chips left out planted, it is not."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness as H

SEED = 2 ** 33 + 17
CELL = "zamba2-7b.train.fsdp4"


@pytest.fixture
def cell(monkeypatch):
    """The cell at the smoke sizes (two rows a device), with the program's
    registry and the peak table steered to them."""
    import repro.configs.base as B
    import repro.launch.train as T
    monkeypatch.setattr(H, "peak", lambda kind: {"bf16_flops": 1e12})
    get = B.get_smoke_config
    monkeypatch.setattr(B, "get_config", get)
    monkeypatch.setattr(T, "get_config", get)
    c = H.resolve(CELL)
    cfg = get(c.config["program_arch"])
    m = {k: getattr(cfg, k, v) for k, v in c.config["model"].items()}
    m["hybrid_layer_ids"] = list(m["hybrid_layer_ids"])
    c.config = dict(c.config, model=m)
    run = dict(c.config["train"]["run"], ce_block_v=64)
    c.config["train"] = dict(c.config["train"], run=run)
    n = len(jax.devices())
    c.traffic = dict(c.traffic, seq_len=128, global_batch=2 * n,
                     mean_doc_len=64, mesh=[n, 1])
    # limits for the smoke widths, set as the cell's are (above the
    # geometric middle of the largest sound reading and the smallest
    # fault's): bfloat16 here puts a sound run's gradient and change
    # 0.013-0.076 and 0.017-0.024 from the reference (the batches' order
    # moves them), 10-30x the readings at the published widths, so the
    # cell's 0.03 and 0.005 would fail a sound run; the faults below read
    # from 0.157 (gradient) and 0.065 (change), both the control's, but
    # for the gradient with the embeddings dropped (0.051; change 0.30)
    c.limits = dict(c.limits, grad_gap=0.12, change_gap=0.04)
    return c


def run_cell(cell, seconds=1.0):
    r = H.Run(cell, SEED, seconds, False)
    H.driver(cell.traffic).run(r, H.CompileCounter())
    return r


def failed(r):
    return {c["name"] for c in r.checks if not c["ok"]}


def test_sound_run_is_correct(cell):
    r = run_cell(cell)
    assert r.correct, r.checks
    assert r.attempted > 0 and r.failed == 0


def _break_block(monkeypatch, broken):
    from repro.models import hybrid
    real = hybrid._shared_block
    monkeypatch.setattr(hybrid, "_shared_block",
                        lambda *a: real(*broken(*a)))


def test_embeddings_dropped(cell, monkeypatch):
    _break_block(monkeypatch, lambda sp, hp, cfg, run, x, emb, *a: (
        sp, hp, cfg, run, x, jnp.zeros_like(emb), *a))
    assert failed(run_cell(cell))


def test_adapters_left_out(cell, monkeypatch):
    _break_block(monkeypatch, lambda sp, hp, *a: (
        sp, {k: v for k, v in hp.items() if k != "adapter"}, *a))
    assert failed(run_cell(cell))


def test_one_shared_block_for_both(cell, monkeypatch):
    from repro.models import hybrid
    real = hybrid._run

    def first_block_only(params, *a, **kw):
        shared = jax.tree.map(lambda w: w.at[1:].set(w[0]),
                              params["shared"])
        return real(dict(params, shared=shared), *a, **kw)
    monkeypatch.setattr(hybrid, "_run", first_block_only)
    assert failed(run_cell(cell))


def _planted_is_not_correct(cell, fault):
    """The reference with ``fault`` planted in the program's place, on the
    batches a sound run consumed (``control_sharded.readings``): some
    number passes its limit."""
    from chipbench import control_sharded
    g = control_sharded.readings(run_cell(cell), [fault])[fault]
    assert any(g[k] > cell.limits[k] for k in g if k in cell.limits), g


def test_control_is_not_correct(cell):
    _planted_is_not_correct(cell, "control")


def test_no_exchange_is_not_correct(cell):
    """Each chip's update made from its own rows' gradient: the reference
    trained on the first chip's rows (four rows a chip here, so that the
    reference takes them one a chip)."""
    n = len(jax.devices())
    cell.traffic = dict(cell.traffic, global_batch=4 * n)
    _planted_is_not_correct(cell, "no_exchange")
